"""The paced cell's admission host time per tick: the reading of ``admit_host_ms.sat``, split by the
end-to-end metric it moves."""

from harness.spec import load_reader

read = load_reader("admit_host_ms.sat")
