"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / (traced window).

Read as the chat cell reads it (bench/metrics/idle_share.chat.py)."""

from harness import spec

read = spec.load_module("metrics", "idle_share.chat").read
