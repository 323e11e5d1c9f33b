"""The readings the limits are set from, at a cell's own size on the
chip (PERF.md lists them; the benchmark's own runs never run this).

    python3 -m perfbench.control --workload <cell> --seeds 12 --control-seeds 3

For each seed it reads the program against the plain reference (the
lower readings), and on the first ``--control-seeds`` of them the
control: the reference computed in the nearest precision below the one
the configuration states (bfloat16 for both configurations' float32,
as their ``precision.control`` says), put in the program's place.
Training also reads the planted fault "rows left out of the gradient,
the mean taken over the rest" in the reference: half of the batch on
one device, one device's share of it under a ``mesh`` (the rows of the
last of ``dp`` devices, as if its gradient never reached the
all-reduce).  One process: the program's seeds first, on every device
of the cell as the mix's ``mesh`` says, then, with its state freed, the
reference's.
"""
import argparse
import json
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def say(text):
    print(text, flush=True)


def train(cell, cfg, mix, dims, seeds, n_control, devices):
    import jax
    import jax.numpy as jnp
    from . import check, harness, traffic
    from .runners import train as runner
    ref = harness.module("reference", cfg["reference"])
    adapter = harness.module("adapters", cfg["adapter"])
    cfg = dict(cfg, use_flash=mix.get("use_flash", False))
    batches = {s: traffic.mlm_batches(mix, dims["vocab_size"], s)[:3]
               for s in seeds}
    mesh = mix.get("mesh", {})
    program = adapter.build(cfg, dims, batches[seeds[0]][0], devices, mesh)
    shares = max(2, mesh.get("dp", 1))
    kept_rows = mix["batch"] * (shares - 1) // shares
    got = {}
    for s in seeds:
        got[s] = runner.first_steps(program, ref, dims, s, batches[s])
        say(f"program seed {s}: losses {got[s]['losses']}")
    program.free()
    del program
    sizes = ref.leaf_sizes(dims)

    def readings(i):
        # a seed's reference, control and fault on one device; the seeds
        # go round robin over the cell's devices, one thread a device and
        # one seed at a time on it
        s = seeds[i]
        with jax.default_device(devices[i % len(devices)]):
            want = ref.train_steps(dims, cfg["optimizer"], s, batches[s],
                                   mix["reference_rows"])
            row = {"seed": s,
                   "program": check.train_numbers(got[s], want, sizes)}
            if i < n_control:
                low = ref.train_steps(dims, cfg["optimizer"], s, batches[s],
                                      mix["reference_rows"],
                                      dtype=jnp.bfloat16)
                row["control"] = check.train_numbers(low, want, sizes)
                lost = ref.train_steps(dims, cfg["optimizer"], s, batches[s],
                                       mix["reference_rows"],
                                       keep_rows=kept_rows)
                row["fault_rows_left_out"] = check.train_numbers(lost, want,
                                                                 sizes)
                row["rows_kept"] = kept_rows
        row["leaves"] = {"program": got[s], "reference": want}
        say("READING " + json.dumps(row))
        return row

    n = len(devices)
    with ThreadPoolExecutor(n) as pool:
        lanes = pool.map(
            lambda d: {i: readings(i) for i in range(d, len(seeds), n)},
            range(n))
    rows = {i: row for lane in lanes for i, row in lane.items()}
    return [rows[i] for i in range(len(seeds))]


def _gap_readings(gaps):
    """The widest gap, which ``correct`` compares today, and three
    numbers of the same gaps that may tell the stated precision from
    the control where the widest does not (PERF.md, Open questions)."""
    return {"token_gap": float(gaps.max()),
            "tokens_off_best": int((gaps > 0).sum()),
            "gap_sum": float(gaps.sum()),
            "gap_mean": float(gaps.mean())}


def serve(cell, cfg, mix, dims, seeds, n_control, devices, seconds):
    import jax.numpy as jnp
    from . import harness
    from .runners import serve as runner
    ref = harness.module("reference", cfg["reference"])
    adapter = harness.module("adapters", cfg["adapter"])
    V = dims["vocab_size"]
    served = jnp.dtype(cfg["precision"]["params"])
    program = adapter.build(cfg, dims, mix["serving"],
                            ref.init_weights(dims, seeds[0]))
    runner.warm_up(program, mix, V, seeds[0])
    samples = {}
    for s in seeds:
        w = ref.init_weights(dims, s)
        program.adapter.params = adapter._to_program(
            {k: v.astype(served) for k, v in w.items()}, dims,
            program.adapter.params["pos"])
        del w
        records, t0, t1, compiled = runner.drive(program, mix, V, s, seconds)
        ended = runner.measure(records, t0, t1, 0.0)[0]
        samples[s] = runner.sample_for_check(ended, mix["check_requests"], s)
        say(f"program seed {s}: {len(ended)} requests ended, "
            f"{sum(r.failed for r in ended)} failed, {compiled} compiled")
    program.free()
    del program
    max_len = mix["prompt_len"]["hi"] + mix["new_tokens"]["hi"]
    out = []
    for s in seeds:
        score = ref.make_scorer(dims, s, max_len, mix["new_tokens"]["hi"])
        logits = [score(r.prompt, r.tokens) for r in samples[s]]
        gaps = np.concatenate([ref.gaps(lg, r.tokens)
                               for lg, r in zip(logits, samples[s])])
        row = {"seed": s, "tokens": int(gaps.size),
               "program": _gap_readings(gaps)}
        if s in seeds[:n_control]:
            low = ref.make_scorer(dims, s, max_len, mix["new_tokens"]["hi"],
                                  dtype=jnp.bfloat16)
            row["control"] = _gap_readings(np.concatenate([
                ref.gaps(lg, low(r.prompt, r.tokens).argmax(-1))
                for lg, r in zip(logits, samples[s])]))
            del low
        say("READING " + json.dumps(row))
        out.append(row)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(prog="perfbench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    import jax
    from mxnet_tpu import compile_cache
    from . import harness
    compile_cache.enable_jax_persistent_cache()
    cell, cfg, mix = harness.load_cell(args.workload)
    devices = jax.devices()[:cell["chips"]]
    if devices[0].platform != "tpu" and not args.rehearsal:
        say("perfbench.control: no TPU")
        return 2
    dims = cfg["toy"] if args.rehearsal else cfg["dims"]
    mix = mix["toy"] if args.rehearsal else mix
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    if cfg["kind"] == "train":
        rows = train(cell, cfg, mix, dims, seeds, args.control_seeds, devices)
    else:
        rows = serve(cell, cfg, mix, dims, seeds, args.control_seeds,
                     devices, args.seconds)
    say("READINGS " + json.dumps({"workload": args.workload, "device":
                                  harness.device_info(devices),
                                  "rehearsal": args.rehearsal, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
