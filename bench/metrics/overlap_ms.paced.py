"""The paced cell's overlapped retirement time per tick: the reading of ``overlap_ms.sat``,
split by the end-to-end metric it moves."""

from harness.spec import load_reader

read = load_reader("overlap_ms.sat")
