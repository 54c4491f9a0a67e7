"""The paced cell's per-lane host time per tick: the reading of ``lane_host_ms.sat``, split by the
end-to-end metric it moves."""

from harness.spec import load_reader

read = load_reader("lane_host_ms.sat")
