"""The paced cell's host time per tick: the reading of
``tick_host_ms.sat``, split by the end-to-end metric it moves."""

from harness.spec import load_reader

read = load_reader("tick_host_ms.sat")
