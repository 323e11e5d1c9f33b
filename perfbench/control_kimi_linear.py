"""What ``perfbench.control`` cannot read for the ``kimi-linear-48b-a3b``
cells, at a cell's own size on the chip (PERF.md lists the readings;
the benchmark's own runs never run this).

    python3 -m perfbench.control_kimi_linear --workload <cell> --seeds 3

For each seed: the tokens whose chosen set of experts differs between
the program's first forward pass and the reference's, by expert layer
(the program's matmuls round to bfloat16 ahead of the float32 router, so
a token whose eighth and ninth experts are nearly tied may choose the
other); and the reference with each of
:data:`reference.kimi_linear.FAULTS` planted in its layers, put in the
program's place: every one has to fail a limit of the cell (a row
carries the verdict and every leaf's gradient norm, the sound
reference's beside them, so that a limit can be set from all leaves and
not from the worst alone).  ``--faults-only`` skips the program (no
step is compiled).
"""
import argparse
import json
import sys

import numpy as np

from .control_mellum import program_choices, say


def main(argv=None):
    ap = argparse.ArgumentParser(prog="perfbench.control_kimi_linear")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_400_000_007)
    ap.add_argument("--faults-only", action="store_true")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    import jax
    from mxnet_tpu import compile_cache
    from . import check, harness, traffic
    from .adapters import kimi_linear as adapter
    from .reference import kimi_linear as ref
    compile_cache.enable_jax_persistent_cache()
    cell, cfg, mix = harness.load_cell(args.workload)
    devices = jax.devices()[:cell["chips"]]
    if devices[0].platform != "tpu" and not args.rehearsal:
        say("perfbench.control_kimi_linear: no TPU")
        return 2
    dims = cfg["toy"] if args.rehearsal else cfg["dims"]
    mix = mix["toy"] if args.rehearsal else mix
    limits = cell["toy_limits" if args.rehearsal else "limits"]
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    batches = {s: traffic.mlm_batches(mix, dims["vocab_size"], s)[:3]
               for s in seeds}
    chosen = {}
    if not args.faults_only:
        program = adapter.build(dict(cfg, use_flash=True), dims,
                                batches[seeds[0]][0], devices,
                                mix.get("mesh", {}))
        for s in seeds:
            program.load_weights(ref.init_weights(dims, s))
            chosen[s] = program_choices(program, batches[s][0][0])
        program.free()
        del program
    sizes = ref.leaf_sizes(dims)
    rows = []
    for s in seeds:
        row = {"seed": s}
        if s in chosen:
            want_ids = np.asarray(ref.chosen_experts(dims, s,
                                                     batches[s][0][0]))
            differ = (np.sort(chosen[s], -1)
                      != np.sort(want_ids, -1)).any(-1)
            row.update(tokens=int(differ.shape[1]),
                       chosen_set_differs_by_layer=differ.sum(-1).tolist())
        want = ref.train_steps(dims, cfg["optimizer"], s, batches[s],
                               mix["reference_rows"])
        for fault in ref.FAULTS[1:]:
            got = ref.train_steps(dims, cfg["optimizer"], s, batches[s],
                                  mix["reference_rows"], fault=fault)
            numbers, where = check.train_numbers(got, want, sizes)
            ok, table = check.verdict(
                numbers, {k: v for k, v in limits.items()
                          if k.endswith("_gap")})
            row["fault_" + fault] = {"passes": ok, "checks": table,
                                     "where": where,
                                     "grad_norms": got["grad_norms"]}
        row["reference_grad_norms"] = want["grad_norms"]
        say("READING " + json.dumps(row))
        rows.append(row)
    say("READINGS " + json.dumps({"workload": args.workload, "device":
                                  harness.device_info(devices),
                                  "rehearsal": args.rehearsal, "rows": rows}))
    caught = all(not row["fault_" + f]["passes"]
                 for row in rows for f in ref.FAULTS[1:])
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
