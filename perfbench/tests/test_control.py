"""The control — the plain reference in the precision step below the
configuration's (weights through fp8) — has to come out not correct under
each cell's comparison, while the program passes.  Kept at smoke size;
the same readings at the cells' own sizes come from
``perfbench/tools/control.py`` on the chip."""

import pytest

import smoke
from harness import traffic as gen


@pytest.mark.parametrize("workload", ["qwen3-4b.chat",
                                      "mamba2-2.7b.chat-burst"])
def test_serving_control_fails_program_passes(workload):
    cell = smoke.cell(workload)
    drv = cell.runner()
    srv = drv.Serve(cell, 99)
    srv.warm(99)
    reqs = gen.serve_requests(cell.traffic, 99, 2.0, srv.cfg.vocab_size)
    out = srv.window(reqs, 2.0)
    srv.free_program_state()
    res = srv.compare(out, 99, cell.traffic["check"]["requests"],
                      control=True)
    limit = cell.config["limits"]["logit_gap"]
    print(workload, "program", res["program"], "control", res["control"])
    assert max(res["program"]) <= limit < max(res["control"])


def test_head_control_fails_program_passes():
    cell = smoke.cell("qwen3-4b.head-distill")
    hv = cell.runner().HeadVJP(cell, 99)
    last = hv.call(hv.blocks, hv.h, hv.dc)
    hv.free_program_state()
    prog = hv.compare(last)
    ctl = hv.compare(None, control=True)
    lim = cell.config["limits"]
    print("program", prog, "control", ctl)
    assert all(prog[k] <= lim[f"head_{k}_rel_err"] for k in prog)
    assert any(ctl[k] > lim[f"head_{k}_rel_err"] for k in ctl)
