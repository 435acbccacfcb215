"""The traffic generator: the same seed gives the same requests, and
every seed the same sizes in the same order (at the same arrivals in an
open loop) with other prompt tokens."""
import numpy as np
import pytest

from bench_helpers import BENCH, MIXES, harness

import generator

SEED = 2 ** 31 + 99   # more than 32 signed bits


def _mix(name):
    return harness.load_json(BENCH / "traffic" / f"{name}.json")


def _sizes(stream, n):
    return [(len(it.prompt), it.max_new) for it in map(stream.get, range(n))]


@pytest.mark.parametrize("loop", ["backlog", "open"])
def test_same_seed_same_requests(loop):
    a = generator.Stream(MIXES[loop], SEED, 1000, 5.0)
    b = generator.Stream(MIXES[loop], SEED, 1000, 5.0)
    for x, y in zip(map(a.get, range(40)), map(b.get, range(40))):
        assert x.max_new == y.max_new and x.arrival_s == y.arrival_s
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("loop", ["backlog", "open"])
def test_other_seed_same_work_in_another_order(loop):
    """Another seed: the same sizes in the same order (a fixed trace), and
    other prompt tokens; across blocks the order changes."""
    n = 16 if loop == "backlog" else round(20.0 * 5.0)
    a = generator.Stream(MIXES[loop], 1, 1000, 5.0)
    b = generator.Stream(MIXES[loop], 2, 1000, 5.0)
    assert _sizes(a, 2 * n) == _sizes(b, 2 * n)
    first, second = _sizes(a, n), _sizes(a, 2 * n)[n:]
    for k in (0, 1):
        assert sorted(x[k] for x in first) == sorted(x[k] for x in second)
    assert first != second
    if loop == "open":
        np.testing.assert_array_equal(a.arrivals, b.arrivals)
    assert not np.array_equal(a.get(0).prompt, b.get(0).prompt)


def test_open_window_holds_rate_times_seconds():
    mix = dict(_mix("prefill-open"), rate_per_s=4.0)
    s = generator.Stream(mix, SEED, 92416, 40.0)
    arr = s.arrivals[:s.n_window]
    assert s.n_window == 160
    assert (np.diff(s.arrivals) > 0).all()
    assert arr[-1] < 40.0 <= s.arrivals[s.n_window]
    assert np.isclose(arr[-1], 40.0 - 0.5 / 4.0)


@pytest.mark.parametrize("name", ["decode-sat", "prefill-open"])
def test_mix_lengths_fit_the_cache(name):
    """A prompt and its output fit s_max - 1 positions (2047), so the
    program never cuts an output short."""
    mix = _mix(name)
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] <= 2047
    lens = generator.lengths(mix["prompt_tokens"], 64)
    assert lens.min() >= mix["prompt_tokens"]["min"]
    assert np.isclose(np.median(lens), mix["prompt_tokens"]["median"],
                      rtol=0.05)


def test_gaps_have_the_mix_cv():
    g = generator.gaps(4.0, 2.0, 4000)
    assert np.isclose(g.mean(), 0.25, rtol=0.02)
    assert np.isclose(g.std() / g.mean(), 2.0, rtol=0.1)
