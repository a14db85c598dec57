"""A whole run, past the look for a chip, with the timed path broken
underneath: ``correct`` has to come out false.  Faults: a token or an
answer altered where it is produced; half of the batch left out."""

import json

import jax
import jax.numpy as jnp
import pytest

import run
import smoke
from harness import peaks as pk

V5E = pk.peaks("TPU v5 lite")


def _result(capsys, monkeypatch, cell, seconds=2.0):
    monkeypatch.setattr(pk, "peaks", lambda kind: V5E)
    capsys.readouterr()
    assert run.execute(cell, smoke.args(seconds=seconds),
                       jax.devices()) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["qwen3-4b.chat",
                                      "mamba2-2.7b.chat-burst"])
def test_serving_sound_run_is_correct(workload, capsys, monkeypatch):
    cell = smoke.cell(workload)
    line = _result(capsys, monkeypatch, cell)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}


def test_serving_token_altered(capsys, monkeypatch):
    from repro.serve.batcher import ContinuousBatcher
    orig = ContinuousBatcher._sample

    def altered(self, slot, row, now):
        reason = orig(self, slot, row, now)
        if len(slot.out) == 3:
            slot.out[-1] = (slot.out[-1] + 1) % self.cfg.vocab_size
            slot.pending = slot.out[-1]
        return reason

    monkeypatch.setattr(ContinuousBatcher, "_sample", altered)
    line = _result(capsys, monkeypatch, smoke.cell("qwen3-4b.chat"))
    assert line["correct"] is False


def test_serving_half_batch_left_out(capsys, monkeypatch):
    from repro.serve import batcher
    orig = batcher.jitted_decode_step

    def half(cfg, **kw):
        step = orig(cfg, **kw)

        def broken(params, state, tokens):
            out, new = step(params, state=state, tokens=tokens)
            n = out.shape[0]
            return out.at[n // 2:].set(0), new
        return broken

    monkeypatch.setattr(batcher, "jitted_decode_step", half)
    line = _result(capsys, monkeypatch,
                   smoke.cell("mamba2-2.7b.chat-burst"))
    assert line["correct"] is False


def _head_call_patch(monkeypatch, breaker):
    drv = smoke.cell("qwen3-4b.head-distill").runner()
    init = drv.HeadVJP.__init__

    def patched(self, cell, seed):
        init(self, cell, seed)
        good = self.call
        self.call = lambda *a: breaker(good, *a)
    monkeypatch.setattr(drv.HeadVJP, "__init__", patched)


def test_head_sound_run_is_correct(capsys, monkeypatch):
    line = _result(capsys, monkeypatch, smoke.cell("qwen3-4b.head-distill"))
    assert line["correct"] is True
    assert set(line["metrics"]) == {"call_ms", "setup_s"}


def test_head_answer_altered(capsys, monkeypatch):
    def breaker(good, blocks, h, dc):
        out, db, dh = good(blocks, h, dc)
        return out.at[0, 0, 0].add(jnp.bfloat16(1.0)), db, dh
    _head_call_patch(monkeypatch, breaker)
    line = _result(capsys, monkeypatch, smoke.cell("qwen3-4b.head-distill"))
    assert line["correct"] is False


def test_head_half_batch_left_out(capsys, monkeypatch):
    def breaker(good, blocks, h, dc):
        b = h.shape[0] // 2
        out, db, dh = good(blocks, h[:b], dc[:b])
        # the mean over the half that ran, scaled to stand for the whole
        return (jnp.concatenate([out, out]), db * 2,
                jnp.concatenate([dh, dh]))
    _head_call_patch(monkeypatch, breaker)
    line = _result(capsys, monkeypatch, smoke.cell("qwen3-4b.head-distill"))
    assert line["correct"] is False
