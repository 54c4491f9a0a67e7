"""The paced cell's fetch time per tick: the reading of ``fetch_ms.sat``, split by the
end-to-end metric it moves."""

from harness.spec import load_reader

read = load_reader("fetch_ms.sat")
