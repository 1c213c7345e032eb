"""The traffic generator: the same schedule for every seed, the same
requests for the same seed, and the mix's bounds kept."""

import json

import numpy as np
import pytest

from benchlib import traffic
from benchlib.spec import BENCH_DIR


def mix(name):
    return json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())


def key(reqs):
    return [(r.rid, r.gen, r.arrival, r.prompt.tobytes()) for r in reqs]


def test_same_seed_same_requests():
    for name in ("reasoning", "longdoc"):
        m = mix(name)
        a = traffic.generate(m, 32064, 2**31 + 12345)
        b = traffic.generate(m, 32064, 2**31 + 12345)
        assert key(a) == key(b)


def test_every_seed_gets_the_same_schedule():
    m = mix("reasoning")
    a = traffic.generate(m, 32064, 1)
    b = traffic.generate(m, 32064, 3_000_000_000)
    assert key(a) != key(b)
    shape = [(r.prompt.size, r.gen, r.arrival) for r in a]
    assert shape == [(r.prompt.size, r.gen, r.arrival) for r in b]


def test_schedule_prefix_and_rate_stretch():
    m = mix("longdoc")
    full = traffic.schedule(m)
    head = traffic.schedule(dict(m, n_requests=40))
    assert (head.plens == full.plens[:40]).all()
    assert (head.gens == full.gens[:40]).all()
    half = traffic.schedule(m, rate=m["arrivals"]["rate_per_step"] / 2)
    assert np.allclose(half.offsets, 2 * full.offsets)


def test_lengths_stay_in_the_mix_bounds():
    for name in ("reasoning", "longdoc"):
        m = mix(name)
        reqs = traffic.generate(m, 100, 7)
        geo = m["geometry"]
        for r in reqs:
            assert m["prompt_len"]["min"] <= r.prompt.size \
                <= m["prompt_len"]["max"]
            assert m["output_len"]["min"] <= r.gen <= m["output_len"]["max"]
            assert r.prompt.size + r.gen <= geo["max_len"]


def test_schedule_follows_the_mix():
    """The drawn schedule's medians and mean gap lie near the mix's."""
    for name in ("reasoning", "longdoc"):
        m = mix(name)
        s = traffic.schedule(m)
        for lens, dist in ((s.plens, m["prompt_len"]),
                           (s.gens, m["output_len"])):
            assert abs(np.median(lens) / dist["median"] - 1) < 0.15
        warm = m["warm_requests"]
        gap = np.diff(s.offsets[warm - 1:]).mean()
        assert abs(gap * m["arrivals"]["rate_per_step"] - 1) < 0.2


def test_warmup_covers_every_mixed_width():
    m = mix("reasoning")
    widths = [r.prompt.size // m["geometry"]["chunk_size"]
              for r in traffic.warmup_requests(m)]
    assert widths == [1, 2, 4, 8, 16]
    first = traffic.generate(m, 100, 1)[0].arrival
    assert first >= traffic.warmup_steps(m)


def test_knee_is_the_load_below_the_plateau():
    import knee

    def rows(admitted):
        return [{"load": 0.8 + 0.1 * i, "admitted": a, "rate_per_step": i}
                for i, a in enumerate(admitted)]
    assert knee.knee_row(rows([85, 95, 108, 112, 112, 112]))["load"] == \
        pytest.approx(1.0)
    assert knee.knee_row(rows([112, 112, 112])) is None      # sweep lower
    assert knee.knee_row(rows([85, 95, 108, 112])) is None   # sweep higher
