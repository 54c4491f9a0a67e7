"""The paced cell's device idle share: the reading of
``device_idle_share.sat``, split by the end-to-end metric it moves."""

from harness.spec import load_reader

read = load_reader("device_idle_share.sat")
