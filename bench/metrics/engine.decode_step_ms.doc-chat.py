"""Median engine decode step in the window, as the engine times it:
the jitted step's dispatch through the host read of every slot's
logits.

Read as the chat cell reads it
(bench/metrics/engine.decode_step_ms.chat.py)."""

from harness import spec

read = spec.load_module("metrics", "engine.decode_step_ms.chat").read
