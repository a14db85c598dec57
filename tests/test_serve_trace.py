"""The serving loop's own spans and counters: ``ContinuousBatcher`` leaves
``serve.*`` spans in the profiler's trace, nested by cause and carrying
each request's ``rid``; they agree with the batcher's counters; the
``pages_in_use_sum`` counter matches a count by hand; and tracing changes
no output and no counter."""

import collections
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import lm
from repro.models.layers import init_sparse_linear
from repro.serve import (BatcherConfig, ContinuousBatcher, Request,
                         RequestQueue, SparseLogitHead, jitted_prefill)
from repro.serve.paged_cache import pages_for

PAGE = 4
# (prompt length, new tokens, arrival step): two slots for three requests,
# so the third is admitted when the first retires
REQUESTS = [(6, 3, 0.0), (8, 4, 0.0), (5, 2, 1.0)]


def _engine():
    cfg = get_smoke_config("qwen3-4b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    w = init_sparse_linear(jax.random.PRNGKey(7), cfg.d_model,
                           cfg.vocab_padded, block_shape=(64, 64),
                           block_density=0.5)
    queue = RequestQueue()
    rng = np.random.default_rng(0)
    for n, new, t in REQUESTS:
        assert queue.submit(Request(
            tokens=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
            max_new_tokens=new, arrival=t))
    return ContinuousBatcher(
        params, cfg, queue,
        BatcherConfig(max_slots=2, page_size=PAGE, n_pages=32, max_seq=16),
        head=SparseLogitHead.build(w))


def _counters(eng):
    return dict(steps=eng.steps, rounds=eng.rounds, admitted=eng.admitted,
                occupancy_sum=eng.occupancy_sum,
                pages_in_use_sum=eng.pages_in_use_sum,
                memory=eng.memory_stats(), faults=eng.fault_stats())


def _serve_spans(path):
    """[(name, start_ns, end_ns, args)] of the ``serve.*`` host spans."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _parents(spans):
    """The index of the innermost span enclosing each span (None at the
    top): spans of one thread nest, so a stack over start order works."""
    out, stack = [None] * len(spans), []
    for i in sorted(range(len(spans)),
                    key=lambda i: (spans[i][1], -spans[i][2])):
        while stack and spans[stack[-1]][2] < spans[i][2]:
            stack.pop()
        out[i] = stack[-1] if stack else None
        stack.append(i)
    return out


def _children(spans, parents):
    kids = collections.defaultdict(list)
    for i, p in enumerate(parents):
        if p is not None:
            kids[p].append(spans[i])
    return kids


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The same requests served twice: once under the profiler, once
    without it."""
    plain = _engine()
    plain_out = plain.run()
    traced = _engine()
    log_dir = str(tmp_path_factory.mktemp("serve_trace"))
    jax.profiler.start_trace(log_dir)
    try:
        traced_out = traced.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return {"plain": (plain, plain_out), "traced": (traced, traced_out),
            "spans": _serve_spans(path)}


@pytest.mark.tier1
def test_spans_nest_by_cause(runs):
    allowed = {"serve.round": {None},
               "serve.admit": {"serve.round"},
               "serve.prefill": {"serve.admit"},
               "serve.prepare": {"serve.round"},
               "serve.decode": {"serve.round"},
               "serve.head": {"serve.admit", "serve.round"},
               "serve.sample.draw": {"serve.admit", "serve.round"},
               "serve.fetch": {"serve.admit", "serve.round"},
               "serve.sample": {"serve.admit", "serve.round"}}
    spans = runs["spans"]
    parents = _parents(spans)
    assert {s[0] for s in spans} == set(allowed)
    for span, p in zip(spans, parents):
        assert (spans[p][0] if p is not None else None) \
            in allowed[span[0]], span
    # an admission prefills, scores the prefill, draws on the device,
    # fetches and records its token once; recording opens no span
    kids = _children(spans, parents)
    for i, span in enumerate(spans):
        names = sorted(k[0] for k in kids[i])
        if span[0] == "serve.admit":
            assert names == ["serve.fetch", "serve.head", "serve.prefill",
                             "serve.sample", "serve.sample.draw"]
        if span[0] in ("serve.sample", "serve.sample.draw"):
            assert names == []


@pytest.mark.tier1
def test_spans_agree_with_counters(runs):
    eng, comps = runs["traced"]
    spans = runs["spans"]
    kids = _children(spans, _parents(spans))
    count = collections.Counter(s[0] for s in spans)
    assert count["serve.round"] == eng.rounds
    assert count["serve.admit"] == eng.admitted == len(REQUESTS)
    assert count["serve.decode"] == eng.steps > 0
    assert count["serve.prepare"] == eng.steps
    assert sorted(s[3]["round"] for s in spans
                  if s[0] == "serve.round") == list(range(eng.rounds))
    # the decode span carries the live slots; they add up to occupancy
    assert sum(s[3]["live"] for s in spans
               if s[0] == "serve.decode") == eng.occupancy_sum
    # once per decoding round: one decode, one head, one draw over the
    # live slots, one fetch of ids and flags (4 + 1 bytes a slot), and
    # one sample per live slot
    for i in (i for i, s in enumerate(spans) if s[0] == "serve.round"):
        names = collections.Counter(k[0] for k in kids[i])
        decode = [k for k in kids[i] if k[0] == "serve.decode"]
        if not decode:
            assert names.keys() <= {"serve.admit"}
            continue
        assert names["serve.decode"] == names["serve.head"] == \
            names["serve.sample.draw"] == names["serve.fetch"] == \
            names["serve.prepare"] == 1
        live = decode[0][3]["live"]
        assert names["serve.sample"] == live
        (draw,) = [k for k in kids[i] if k[0] == "serve.sample.draw"]
        assert draw[3]["live"] == live
        (fetch,) = [k for k in kids[i] if k[0] == "serve.fetch"]
        assert fetch[3]["bytes"] == eng.bcfg.max_slots * 5
    # an admission draws and fetches one row
    for i in (i for i, s in enumerate(spans) if s[0] == "serve.admit"):
        assert {k[0]: k[3] for k in kids[i]
                if k[0] in ("serve.sample.draw", "serve.fetch")} == \
            {"serve.sample.draw": {"live": 1}, "serve.fetch": {"bytes": 5}}
    # the request's rid on its admission and on each token it sampled
    rids = {c.rid for c in comps}
    admits = [s[3] for s in spans if s[0] == "serve.admit"]
    assert {a["rid"] for a in admits} == rids
    assert sorted(a["prompt_len"] for a in admits) == sorted(
        n for n, _, _ in REQUESTS)
    assert not any(a["resumed"] for a in admits)
    assert collections.Counter(s[3]["rid"] for s in spans
                               if s[0] == "serve.sample") == \
        {c.rid: len(c.tokens) for c in comps}
    prefill_len = {s[3]["rid"]: s[3]["padded_len"] for s in spans
                   if s[0] == "serve.prefill"}
    by_rid = {c.rid: c.prompt_len for c in comps}
    assert prefill_len == {r: pages_for(n, PAGE) * PAGE
                           for r, n in by_rid.items()}


@pytest.mark.tier1
def test_head_spans_carry_tokens_and_tiles(runs):
    """A decode round scores every slot in one token tile; an admission
    scores the prefill's last position, one token in one tile."""
    eng, _ = runs["traced"]
    spans = runs["spans"]
    parents = _parents(spans)
    heads = [(spans[p][0], s[3]) for s, p in zip(spans, parents)
             if s[0] == "serve.head"]
    assert [(a["tokens"], a["tiles"]) for p, a in heads
            if p == "serve.round"] == [(eng.bcfg.max_slots, 1)] * eng.steps
    assert [(a["tokens"], a["tiles"]) for p, a in heads
            if p == "serve.admit"] == [(1, 1)] * eng.admitted


@pytest.mark.tier1
def test_pages_in_use_sum_hand_count(runs):
    """At a request's k-th fused step its slot holds the pages up to the
    one its token is written to: ``(prompt + k - 1) // page + 1``; it
    takes ``max_new - 1`` fused steps (the first token is the
    prefill's)."""
    eng, comps = runs["traced"]
    assert all(c.status == "length" for c in comps)
    hand = sum((n + k - 1) // PAGE + 1
               for n, new, _ in REQUESTS for k in range(1, new))
    assert eng.pages_in_use_sum == hand
    assert eng.memory_stats()["pages_in_use_sum"] == hand
    # each fused step's span carries the count it added, and the pool
    decode = [s[3] for s in runs["spans"] if s[0] == "serve.decode"]
    assert sum(a["pages_in_use"] for a in decode) == hand
    assert {a["pages_in_use"] + a["pages_free"] for a in decode} == \
        {eng.bcfg.n_pages - 1}


@pytest.mark.tier1
def test_tracing_changes_no_output_and_no_counter(runs):
    (plain, plain_out), (traced, traced_out) = runs["plain"], runs["traced"]

    def served(comps):       # rids count up across queues: keep the order
        first = min(c.rid for c in comps)
        return [(c.rid - first, c.tokens, c.status, c.steps) for c in comps]
    assert served(traced_out) == served(plain_out)
    assert _counters(traced) == _counters(plain)


@pytest.mark.tier1
def test_serving_programs_carry_stable_names(runs):
    """The programs a trace names: the fused step, each prefill length,
    the head at decode and on a prefill's output, and the sampler."""
    eng, _ = runs["plain"]
    state = dict(eng.state)
    tokens = jax.numpy.zeros((eng.bcfg.max_slots, 1), jax.numpy.int32)
    hidden, _ = jax.eval_shape(
        lambda p, s, t: eng._step_fn(p, state=s, tokens=t),
        eng.params, state, tokens)
    prompt = {"tokens": jax.numpy.zeros((1, 8), jax.numpy.int32)}
    lowered = {
        "jit_decode_step": eng._step_fn.lower(eng.params, state=state,
                                              tokens=tokens),
        "jit_prefill": jitted_prefill(eng.cfg, 8, return_hidden=True).lower(
            eng.params, batch=prompt),
        "jit_head_decode": eng._head_decode.lower(eng.head.weight, hidden),
        "jit_head_prefill": eng._head_prefill.lower(eng.head.weight, hidden),
        "jit_sample": eng._sampler.lower(
            jax.ShapeDtypeStruct((eng.bcfg.max_slots, 1,
                                  eng.cfg.vocab_padded), jax.numpy.float32),
            eng.keys, -1),
    }
    for name, low in lowered.items():
        assert low.as_text().startswith(f"module @{name} "), name
