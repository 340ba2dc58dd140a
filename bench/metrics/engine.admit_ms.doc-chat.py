"""Engine admission time per admission, in the window: the engine's
own prefill_time counter (bucket prefill, one-token remainder steps,
slot copy, first-token read) over its admissions.

Read as the chat cell reads it (bench/metrics/engine.admit_ms.chat.py)."""

from harness import spec

read = spec.load_module("metrics", "engine.admit_ms.chat").read
