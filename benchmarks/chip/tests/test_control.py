"""The control, the reference computed with every matmul operand in
float8_e4m3fn (the precision below the configurations' bf16), fails
the cell's check, here at the program's smoke size on the CPU.  On the
chip at the cells' sizes it is read by ``calibrate.py``; PERF.md gives
those readings."""
import pytest

import harness
import serving
import smoke

CELLS = {w["name"]: harness.traffic(w["traffic"])["kind"]
         for w in harness.benchmark()["workloads"]}


@pytest.mark.parametrize("cell", [c for c, k in CELLS.items()
                                  if k != "train_steps"])
def test_serving_control_fails(cell, tmp_path):
    ctx = smoke.context(cell, out_dir=tmp_path, seconds=4.0, wider=True)
    drv = harness.load_module("drivers", ctx.traffic["kind"])
    srv = drv.setup(ctx)
    srv.start()
    if ctx.traffic["kind"] == "open_loop":
        import mix
        plan = mix.open_loop(ctx.traffic, ctx.seed, ctx.seconds,
                             ctx.spec.vocab)
        w = drv.window(ctx, srv, plan, ctx.traffic["preroll_s"],
                       ctx.seconds)
    else:
        w = drv.window(ctx, srv, ctx.seed, ctx.seconds)
    srv.stop()
    done = [s for s in w.sent if s.req.finish == "length"]
    picked = serving.sample(done, len(done), ctx.seed)
    prog, _ = serving.token_gaps(ctx.spec, srv.params, picked, srv.s_max)
    ctrl, _ = serving.token_gaps(ctx.spec, srv.params, picked, srv.s_max,
                                 quant="fp8")
    limit = ctx.limits["served_token_gap"]
    assert prog <= limit < ctrl


@pytest.mark.parametrize("cell", [c for c, k in CELLS.items()
                                  if k == "train_steps"])
def test_training_control_fails(cell, tmp_path):
    ctx = smoke.context(cell, out_dir=tmp_path)
    drv = harness.load_module("drivers", ctx.traffic["kind"])
    built = drv.build(ctx)
    job = drv.Job(ctx, built)
    fed, losses, g, d = job.first_steps()
    job.close()
    ref = drv.reference_steps(ctx.spec, ctx.traffic, ctx.seed, fed, built)
    ctrl = drv.reference_steps(ctx.spec, ctx.traffic, ctx.seed, fed, built,
                               quant="fp8")
    prog_nums = drv.compare(losses, g, d, *ref)
    ctrl_nums = drv.compare(*ctrl, *ref)
    assert all(v <= ctx.limits[k] for k, v in prog_nums.items())
    assert any(v > ctx.limits[k] for k, v in ctrl_nums.items()), ctrl_nums
