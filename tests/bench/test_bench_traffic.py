"""The traffic generator: every seed gets the same window of work in
another order, warm-up arrivals fall before the window, a mix that
names a schedule seed replays one order with the run's own token ids,
and an offline mix queues all its requests at the window's start."""

import json

import numpy as np

import bench_tiny
from harness import traffic as T

with open(bench_tiny.ROOT / "bench" / "traffic" / "chat.json") as f:
    CHAT = json.load(f)
SEEDS = (2 ** 31 + 5, 2 ** 33 + 17)


def _split(reqs):
    warm = [r for r in reqs if r.due_s < 0]
    return warm, [r for r in reqs if r.due_s >= 0]


def _gaps(reqs):
    return np.diff([0.0] + [r.due_s for r in reqs])


def test_every_seed_gets_the_same_window_of_work():
    mix = {k: v for k, v in CHAT.items() if k != "schedule_seed"}
    runs = [T.serve_requests(mix, 1000, s, 51.0) for s in SEEDS]
    warm_s = CHAT["arrivals"]["warmup_s"]
    for reqs in runs:
        warm, window = _split(reqs)
        assert all(-warm_s <= r.due_s < 0 for r in warm)
        assert all(r.due_s < 51.0 for r in window)
        assert len(window) == round(CHAT["arrivals"]["rate_per_s"] * 51)
    windows = [_split(reqs)[1] for reqs in runs]
    lens = [sorted(len(r.prompt) for r in w) for w in windows]
    outs = [sorted(r.max_new for r in w) for w in windows]
    assert lens[0] == lens[1] and outs[0] == outs[1]
    assert np.allclose(sorted(_gaps(windows[0])), sorted(_gaps(windows[1])))
    assert [len(r.prompt) for r in windows[0]] != \
        [len(r.prompt) for r in windows[1]]
    p = CHAT["prompt_tokens"]
    assert p["min"] <= min(lens[0]) and max(lens[0]) <= p["max"]


def test_a_schedule_seed_replays_one_order():
    mix = dict(CHAT, schedule_seed=7)
    a, b = (T.serve_requests(mix, 1000, s, 51.0) for s in SEEDS)
    assert [(r.due_s, len(r.prompt), r.max_new) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new) for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_an_offline_mix_is_queued_at_the_start():
    mix = dict(CHAT, arrivals={"process": "all_at_start", "requests": 64})
    reqs = T.serve_requests(mix, 1000, SEEDS[0], 51.0)
    assert len(reqs) == 64 and all(r.due_s == 0.0 for r in reqs)
