"""Run one cell once and exit.

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip and starts no child.  Without a TPU
(or with fewer chips than the cell asks for, or outside a checkout of
the repo) it exits non-zero and prints no result.  The last line of
standard output is the result; the numbers ``correct`` compared, each
beside its limit, are its last key and the last lines of standard
error.  ``--rehearsal`` drives the same control flow at the toy sizes
of the cell's files on whatever backend JAX has, to debug the harness:
it says REHEARSAL, reports no rate and prints no result.
"""
import argparse
import json
import sys
import time
from types import SimpleNamespace

T_START = time.perf_counter()


def parse(argv):
    ap = argparse.ArgumentParser(prog="perfbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    return ap.parse_args(argv)


def say(text):
    print(text, file=sys.stderr, flush=True)


def per_layer(ctx, result, end_to_end):
    """The traced run's metrics: each applicable metric file's reader,
    left out where it finds nothing to read.  A metric whose file lists
    this cell by name has something to read here by its own word: if
    its reader finds nothing, the kernel or program has gone out of the
    reader's sight, and the run fails rather than stay silent."""
    from . import harness, trace_reduce
    trace = result["profiler"].load()
    ctx.trace = trace
    ctx.facts = result["facts"]
    out = {}
    for m in harness.cell_metrics(ctx.cell["name"], end_to_end):
        value = harness.module("readers", m["reader"]).read(m, ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
        elif ctx.cell["name"] in m.get("workloads", ()):
            raise RuntimeError(
                f"perfbench: {m['name']} lists {ctx.cell['name']} but its "
                f"reader {m['reader']} found nothing in the trace")
    device = {"busy_s": trace_reduce.busy_seconds(trace),
              "window_s": trace.window_s}
    breakdown = {"device_ops": trace_reduce.top_ops(trace),
                 "idle_gaps": trace_reduce.idle_gaps(trace)}
    return out, device, breakdown


def main(argv=None):
    args = parse(argv)
    import jax
    from . import check, harness
    try:
        devices = jax.devices()
    except RuntimeError as e:
        say(f"perfbench: no accelerator: {e}")
        return 2
    cell, cfg, mix = harness.load_cell(args.workload)
    on_chip = all(d.platform == "tpu" for d in devices)
    if args.rehearsal:
        say("REHEARSAL: toy sizes, no rate, no result")
    elif "parked" in cell:
        say(f"perfbench: {args.workload} is parked: {cell['parked']}")
        return 2
    elif not on_chip or len(devices) < cell["chips"]:
        say(f"perfbench: {args.workload} needs {cell['chips']} TPU chip(s); "
            f"JAX found {len(devices)} x {devices[0].platform}")
        return 2
    devices = devices[:cell["chips"]]
    try:
        from mxnet_tpu import compile_cache
    except ImportError as e:
        say(f"perfbench: cannot import mxnet_tpu ({e}): run from the root "
            f"of a checkout")
        return 2
    cache = compile_cache.enable_jax_persistent_cache()

    runner = harness.module("runners", cfg["kind"])
    ctx = SimpleNamespace(
        args=args, cell=cell, cfg=cfg, devices=devices, t_start=T_START,
        dims=cfg["toy"] if args.rehearsal else cfg["dims"],
        mix=mix["toy"] if args.rehearsal else mix, note=say,
        peak=None, trace=None, facts=None)
    if not args.rehearsal:
        from . import peaks
        ctx.peak = peaks.peak(devices[0].device_kind)
    result = runner.run(ctx)

    limits = cell["toy_limits" if args.rehearsal else "limits"]
    correct, table = check.verdict(result["numbers"], limits)
    device = harness.device_info(devices)
    device["memory_peak_bytes"] = result["memory_peak"]
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"]}
    if args.rehearsal:
        # no rate off the chip and no device plane in the trace: the
        # profiler's start and stop were rehearsed, no reader runs
        if args.trace:
            say(f"REHEARSAL: traced window "
                f"{result['profiler'].load().window_s:.3f}s")
    elif args.trace:
        metrics, traced, breakdown = per_layer(ctx, result, runner.END_TO_END)
        device.update(traced)
        line.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        line.update(metrics={
            name: {"value": result["metrics"][name], "unit": unit}
            for name, unit in harness.end_to_end_units(runner.END_TO_END)},
            device=device)
    line["checks"] = table
    say(f"jax compile cache: {json.dumps(cache)}; wall "
        f"{time.perf_counter() - T_START:.1f}s")
    say("correct: " + json.dumps(correct) + "  " + "  ".join(
        f"{k}={v[0]} (limit {v[1]})" for k, v in table.items()))
    if args.rehearsal:
        say("REHEARSAL done (no result: this was not the chip): "
            + json.dumps({k: line[k] for k in ("correct", "attempted",
                                               "failed", "checks")}))
        return 0 if correct else 1
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
