"""Seeded traffic reproduces exactly, and seeds change only the order
and contents of the work, never its amount."""
import numpy as np

from bench import generate
from bench.tests import small


def _open(rate=3.0):
    return dict(small.LM_TRAFFIC, loop="open", rate_per_s=rate, pool=64)


def test_same_seed_same_requests():
    for tr in (small.LM_TRAFFIC, _open()):
        a = generate.lm_requests(tr, 2**31 + 17, 512, 10.0)
        b = generate.lm_requests(tr, 2**31 + 17, 512, 10.0)
        assert len(a) == len(b) > 0
        for x, y in zip(a, b):
            assert np.array_equal(x.prompt, y.prompt)
            assert (x.max_new, x.due_s) == (y.max_new, y.due_s)


def test_seeds_share_sizes_and_arrivals():
    tr = _open()
    a = generate.lm_requests(tr, 1, 512, 10.0)
    b = generate.lm_requests(tr, 2, 512, 10.0)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    sizes = lambda rs: sorted((len(r.prompt), r.max_new) for r in rs)  # noqa
    assert sizes(a) == sizes(b)
    assert any(not np.array_equal(x.prompt[:4], y.prompt[:4])
               for x, y in zip(a, b))
    back_a = generate.lm_requests(small.LM_TRAFFIC, 1, 512, 10.0)
    back_b = generate.lm_requests(small.LM_TRAFFIC, 2, 512, 10.0)
    assert sizes(back_a) == sizes(back_b)
    assert all(r.due_s is None for r in back_a)
    # a backlog seed reorders only inside groups: every prefix of whole
    # groups holds the same sizes whatever the seed
    blk = small.LM_TRAFFIC["order_block"]
    for end in range(blk, len(back_a) + 1, blk):
        assert sizes(back_a[:end]) == sizes(back_b[:end])


def test_lengths_stay_inside_the_mix_limits():
    sizes = generate.lm_sizes(small.LM_TRAFFIC)
    lo = min(k["prompt"]["min"] for k in small.LM_TRAFFIC["mix"])
    hi = max(k["prompt"]["max"] for k in small.LM_TRAFFIC["mix"])
    assert sizes[:, 0].min() >= lo and sizes[:, 0].max() <= hi
    assert len(sizes) == small.LM_TRAFFIC["pool"]


def test_arrivals_inside_the_window_at_the_rate():
    t = generate.arrival_times(_open(rate=50.0), 20.0)
    assert np.all(np.diff(t) > 0) and t[0] >= 0 and t[-1] < 20.0
    assert 800 < len(t) < 1200


def test_dit_requests_reproduce_and_stagger():
    tr = small.DIT_TRAFFIC
    f1, b1 = generate.dit_requests(tr, 99, 16)
    f2, b2 = generate.dit_requests(tr, 99, 16)
    for x, y in zip(f1 + b1, f2 + b2):
        assert np.array_equal(x.latent, y.latent)
        assert (x.num_steps, x.t_start, x.cond) == (y.num_steps, y.t_start,
                                                    y.cond)
    assert [r.num_steps for r in f1] == tr["fill_steps"]
    # a partial request keeps the full request's step size
    for r in f1:
        assert abs(r.t_start / r.num_steps
                   - tr["t_start"] / tr["num_steps"]) < 1e-12
    c1 = generate.dit_conds(tr, 99, 16, 128)
    assert c1.shape == (tr["cond_pool"], 16, 128)
    assert np.array_equal(c1, generate.dit_conds(tr, 99, 16, 128))
