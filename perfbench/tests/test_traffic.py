"""The generator gives every seed the same work in another order."""

import numpy as np

from harness import traffic as gen



def test_every_seed_offers_the_same_schedule():
    """Sizes and due times come from the mix's schedule_seed; the run's
    seed draws only the token ids."""
    from harness import registry
    for w in ("qwen3-4b.chat", "mamba2-2.7b.chat-burst"):
        tr = registry.load_cell(w).traffic
        a = gen.serve_requests(tr, 1, 51.0, 1000)
        b = gen.serve_requests(tr, 2**31 + 12345, 51.0, 1000)
        assert len(a) == len(b) == gen.request_count(tr, 51.0)
        assert [(r.due, len(r.tokens), r.max_new) for r in a] == \
            [(r.due, len(r.tokens), r.max_new) for r in b]
        assert any((x.tokens != y.tokens).any() for x, y in zip(a, b))
        assert 0.0 == a[0].due and max(r.due for r in a) < 51.0
        lengths = set(tr["prompt"]["lengths"])
        assert {len(r.tokens) for r in a} <= lengths
        assert all(tr["output"]["min"] <= r.max_new <= tr["output"]["max"]
                   for r in a)
        assert gen.longest_request(tr) >= max(
            len(r.tokens) + r.max_new for r in a)
        other = dict(tr, schedule_seed=tr["schedule_seed"] + 1)
        c = gen.serve_requests(other, 1, 51.0, 1000)
        assert sorted(r.max_new for r in c) == sorted(r.max_new for r in a)
        assert [r.max_new for r in c] != [r.max_new for r in a]


def test_same_seed_same_requests():
    from harness import registry
    tr = registry.load_cell("qwen3-4b.chat").traffic
    a = gen.serve_requests(tr, 7, 20.0, 500)
    b = gen.serve_requests(tr, 7, 20.0, 500)
    assert all(x.due == y.due and (x.tokens == y.tokens).all()
               for x, y in zip(a, b))


def test_bursts_are_burstier_than_poisson():
    from harness import registry
    burst = registry.load_cell("mamba2-2.7b.chat-burst").traffic
    pois = registry.load_cell("qwen3-4b.chat").traffic
    cv = []
    for tr in (pois, burst):
        d = np.diff([r.due for r in gen.serve_requests(tr, 3, 51.0, 100)])
        cv.append(d.std() / d.mean())
    assert cv[1] > 1.5 * cv[0]
