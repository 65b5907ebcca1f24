"""The traffic generator: reproducible from the seed, different across
seeds, and the same work (sizes, arrivals) for every seed."""
import json
from pathlib import Path

import numpy as np
import pytest

from benchlib import traffic
from conftest import CHAT_SESSIONS, SHORT_UNSHARED

MIXES = Path(__file__).resolve().parents[1] / "traffic"
OPEN = {"chat-sessions": CHAT_SESSIONS, "short-unshared": SHORT_UNSHARED}


def mix(name):
    if name in OPEN:
        return OPEN[name]
    return json.loads((MIXES / f"{name}.json").read_text())


def open_plan(name, seed, seconds=40.0):
    return traffic.open_loop(mix(name), 2048, 50304, seed, seconds)


def signature(plan):
    return [(p.arrival, len(p.prompt), p.max_new, p.temperature,
             p.prompt.tobytes()) for p in plan]


@pytest.mark.parametrize("name", ["chat-sessions", "short-unshared"])
def test_open_loop_reproducible_and_seeded(name):
    seed = 2**33 + 12345
    (pre_a, a), (pre_b, b) = open_plan(name, seed), open_plan(name, seed)
    assert signature(a) == signature(b)
    assert all(np.array_equal(x, y) for x, y in zip(pre_a, pre_b))
    _, c = open_plan(name, seed + 1)
    assert signature(a) != signature(c)
    assert all(-mix(name)["lead_in_s"] <= p.arrival < 40.0 for p in a)
    assert all(len(p.prompt) + p.max_new <= 2048 for p in a)


@pytest.mark.parametrize("name", ["chat-sessions", "short-unshared"])
def test_open_loop_same_schedule_for_every_seed(name):
    """Every seed sends the same work in another order: the sessions and
    the gaps between their starts are the seed's to order, the sizes and
    greedy flags are not."""
    runs = [open_plan(name, s)[1] for s in (1, 2, 3)]
    order = [[(len(p.prompt), p.max_new, p.temperature) for p in r]
             for r in runs]
    assert order[0] != order[1] != order[2]
    if "turns" not in mix(name):     # one request a session: all are sent
        assert sorted(order[0]) == sorted(order[1]) == sorted(order[2])
        # the gaps between starts, the last one up to the window's end
        gaps = [np.sort(np.diff([p.arrival for p in r] + [40.0]))
                for r in runs]
        np.testing.assert_allclose(gaps[0], gaps[1])
        np.testing.assert_allclose(gaps[0], gaps[2])


def test_chat_sessions_share_prefixes():
    prewarm, plan = open_plan("chat-sessions", 7)
    assert len(prewarm) == mix("chat-sessions")["tenants"]["count"]
    systems = {p.tobytes() for p in prewarm}
    opened = sum(any(p.prompt[:len(s)].tobytes() == s.tobytes()
                     for s in prewarm) for p in plan)
    assert opened == len(plan) and len(systems) == len(prewarm)


def test_closed_loop_reproducible_and_same_work():
    spec = mix("batch-decode")
    a = traffic.closed_loop(spec, 4096, 100352, 99)
    b = traffic.closed_loop(spec, 4096, 100352, 99)
    c = traffic.closed_loop(spec, 4096, 100352, 100)
    flat = lambda cl: [signature(q) for q in cl]  # noqa: E731
    assert flat(a) == flat(b) and flat(a) != flat(c)
    assert len(a) == spec["clients"]

    def sizes(cl):
        return [[(len(p.prompt), p.max_new, p.temperature) for p in q]
                for q in cl]

    assert sizes(a) == sizes(c)         # the seed draws token ids only
    assert all(spec["output"]["min"] <= p.max_new <= spec["output"]["max"]
               for q in a for p in q)


def test_max_prompt_bounds_every_prompt():
    for name in ("chat-sessions", "short-unshared"):
        n = traffic.max_prompt(mix(name), 2048)
        assert max(len(p.prompt) for p in open_plan(name, 3)[1]) <= n
