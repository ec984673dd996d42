"""The traffic generator: prompts from the seed with the same multiset of
lengths on every seed, and the closed window loop that offers them to an
engine and waits for everything offered."""

import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
from traffic import Traffic  # noqa: E402

CLOSED = {"loop": "closed", "clients": 2, "pod_size": 2,
          "prompt": {"padded_len": 77, "median": 25, "sigma": 0.6,
                     "min_tokens": 4, "max_tokens": 77}}


def test_prompts_are_seeded_padded_and_share_their_lengths():
    a, b, a2 = (Traffic(CLOSED, s, 49408, 77) for s in (1, 2, 1))
    assert np.array_equal(a.prompt(5), a2.prompt(5))
    assert not np.array_equal(a.prompt(5), b.prompt(5))
    assert sorted(a.lengths) == sorted(b.lengths)
    assert 20 <= np.median(a.lengths) <= 30
    p, k = a.prompt(3), int(a.lengths[3])
    assert p.shape == (77,) and p.dtype == np.int32
    assert (p[k:] == 49407).all() and (p[:k] < 49407).all()
    assert Traffic(CLOSED, 1, 512, 16).prompt(0).shape == (16,)


def test_only_the_closed_loop_is_generated():
    with pytest.raises(ValueError, match="closed-loop"):
        Traffic(dict(CLOSED, loop="open"), 1, 49408, 77)


class FakeEngine:
    """Serves whatever is queued, in pods, after ``service_s`` seconds."""

    def __init__(self, pod, service_s):
        self.queue, self.pod, self.service_s = [], pod, service_s
        self.stats = {"generate_s": 0.0}

    def submit(self, rid, tokens, max_new, arrival_tick=0):
        self.queue.append(rid)

    def pending(self):
        return len(self.queue)

    def step(self):
        import time

        pod, self.queue = self.queue[:self.pod], self.queue[self.pod:]
        time.sleep(self.service_s)
        self.stats["generate_s"] += self.service_s
        return [(r, np.zeros(1)) for r in pod]


class NoRecorder:
    def take(self, rids):
        return {r: {} for r in rids}


def test_window_serves_everything_offered():
    t = Traffic(CLOSED, 4, 49408, 77)
    win = harness.serve_window(FakeEngine(2, 0.05), t, 0.3, NoRecorder(),
                               annotate=False)
    assert set(win["done"]) == set(win["due"]) == set(win["prompts"])
    assert all(win["done"][r] >= win["due"][r] for r in win["due"])
    assert all((win["prompts"][r] == t.prompt(r)).all() for r in win["due"])
    # each client's next request is due when its last one returned
    assert sorted(win["due"].values())[:2] == [0.0, 0.0]
    assert max(win["due"].values()) < 0.3 <= max(win["done"].values())
