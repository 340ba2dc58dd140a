"""The benchmark's yardstick: cell lookup, traffic generation, work
counts, peaks, trace reduction and the runners of the two cell kinds.

Nothing here is imported by the program under test; the runners import
the program (``repro``) only to build and drive the system itself.
"""
