"""The batched sampler: ``sample_tokens`` (and its ``jit_sample`` program)
draws every row's token in one call, bit for bit as a per-row loop of
``split`` + ``sample_token`` over chained keys; its finite flags ignore
the padded vocabulary and mark exactly the non-finite or poisoned row;
and the batcher's device-held key chains keep a sampled request's tokens
independent of preemption and of which requests share its batch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import lm
from repro.serve import (BatcherConfig, ContinuousBatcher, FaultSchedule,
                         Request, RequestQueue, SamplingConfig,
                         jitted_sampler, sample_token, sample_tokens)

VOCAB, PADDED, ROWS, ROUNDS = 250, 256, 5, 6
GREEDY = SamplingConfig(temperature=0.0)
SAMPLED = SamplingConfig(temperature=0.8, top_k=40)


def _logits(rnd):
    """bf16 logits with planted ties (rows 0-2 share their best score
    between two columns) and the largest score of every row in a padded
    column, which no draw may return."""
    rng = np.random.default_rng(rnd)
    lg = rng.normal(size=(ROWS, PADDED)).astype(np.float32)
    for b in range(3):
        i, j = sorted(rng.choice(VOCAB, 2, replace=False))
        lg[b, i] = lg[b, j] = 8.0
    lg[:, VOCAB + 1] = 100.0
    return jnp.asarray(lg, jnp.bfloat16)


def _keys():
    base = jax.random.PRNGKey(7)
    return jnp.stack([jax.random.fold_in(base, b) for b in range(ROWS)])


def _per_row(logits, keys, sampling):
    """The per-slot chain the batcher ran before: split, then draw."""
    toks, out = [], []
    for b in range(ROWS):
        key, sub = jax.random.split(keys[b])
        toks.append(int(sample_token(logits[b][None], sub, sampling,
                                     VOCAB)[0]))
        out.append(key)
    return np.asarray(toks, np.int32), np.stack(out)


@pytest.mark.tier1
@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("sampling", [GREEDY, SAMPLED],
                         ids=["greedy", "sampled"])
def test_sample_tokens_matches_per_row_chain(sampling, jitted):
    draw = (lambda lg, k: jitted_sampler(sampling, VOCAB)(lg[:, None], k,
                                                          -1)) if jitted \
        else (lambda lg, k: sample_tokens(lg, k, -1, sampling, VOCAB))
    keys = ref_keys = _keys()
    for rnd in range(ROUNDS):
        logits = _logits(rnd)
        ref_toks, ref_keys = _per_row(logits, ref_keys, sampling)
        toks, finite, keys = draw(logits, keys)
        assert toks.dtype == jnp.int32 and toks.shape == (ROWS,)
        np.testing.assert_array_equal(np.asarray(toks), ref_toks)
        np.testing.assert_array_equal(np.asarray(keys), ref_keys)
        assert np.asarray(finite).all()
        assert (np.asarray(toks) < VOCAB).all()
        if sampling is GREEDY:     # ties go to the first best column
            lg = np.asarray(logits[:, :VOCAB], np.float32)
            np.testing.assert_array_equal(np.asarray(toks),
                                          lg.argmax(axis=-1))


@pytest.mark.tier1
@pytest.mark.parametrize("fault,flagged", [
    ("padded_nan", None), ("real_nan", 3), ("real_inf", 1), ("poison", 2)])
def test_finite_flags_mark_exactly_the_faulty_row(fault, flagged):
    logits = _logits(0)
    poison = -1
    if fault == "padded_nan":
        logits = logits.at[3, VOCAB + 2].set(jnp.nan)
    elif fault == "real_nan":
        logits = logits.at[3, 17].set(jnp.nan)
    elif fault == "real_inf":
        logits = logits.at[1, VOCAB - 1].set(-jnp.inf)
    else:
        poison = flagged
    _, finite, _ = jitted_sampler(GREEDY, VOCAB)(logits[:, None], _keys(),
                                                 poison)
    expect = np.ones(ROWS, bool)
    if flagged is not None:
        expect[flagged] = False
    np.testing.assert_array_equal(np.asarray(finite), expect)


# --------------------------------------------------------------------------
# the batcher's key chains under sampling
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke():
    cfg = get_smoke_config("qwen3-4b")
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(0))


def _serve(smoke, reqs, faults=None, **bcfg):
    """Serve ``(rid, prompt seed, new tokens, arrival)`` requests sampled
    at ``SAMPLED``; the tokens of each, in order.  Rids are fixed, since
    each key chain starts at ``fold_in(engine key, rid)``."""
    cfg, params = smoke
    queue = RequestQueue()
    for rid, seed, new, t in reqs:
        prompt = np.asarray(jax.random.randint(
            jax.random.PRNGKey(seed), (8,), 0, cfg.vocab_size), np.int32)
        assert queue.submit(Request(tokens=prompt, max_new_tokens=new,
                                    arrival=t, rid=rid))
    eng = ContinuousBatcher(params, cfg, queue,
                            BatcherConfig(page_size=4, max_seq=32, **bcfg),
                            sampling=SAMPLED, key=jax.random.PRNGKey(11),
                            faults=faults)
    comps = {c.rid: c for c in eng.run()}
    assert all(c.status == "length" for c in comps.values())
    return eng, [comps[r[0]].tokens for r in reqs]


A, B = (1000, 3, 12, 0.0), (1001, 4, 4, 0.0)


@pytest.mark.tier1
@pytest.mark.slow
@pytest.mark.parametrize("case", ["co_resident", "preempted", "fallback"])
def test_sampled_request_keeps_its_key_chain(smoke, case):
    """A sampled request draws the same tokens alone, beside another
    request, across a preemption, and when a round degrades to the
    static path: its slot's row of the device key array carries its own
    fold_in chain, preemption hands the row back to the request, and the
    static path continues it."""
    _, (alone,) = _serve(smoke, [A], max_slots=2, n_pages=32)
    if case == "co_resident":
        eng, (a, _) = _serve(smoke, [A, B], max_slots=2, n_pages=32)
    elif case == "preempted":   # 5 usable pages: A needs them all
        eng, (a, _) = _serve(smoke, [A, B[:3] + (2.0,)], max_slots=2,
                             n_pages=6)
        assert eng.preemptions >= 1
    else:
        eng, (a, _) = _serve(smoke, [A, B], max_slots=2, n_pages=32,
                             faults=FaultSchedule(transient={2: 3}))
        assert eng.fallbacks == 1
    assert len(alone) == 12
    assert a == alone
