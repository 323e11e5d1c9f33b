"""What ``perfbench.control`` cannot read for the ``mellum2-12b-a2.5b``
cells, at a cell's own size on the chip (PERF.md lists the readings;
the benchmark's own runs never run this).

    python3 -m perfbench.control_mellum --workload <cell> --seeds 3

For each seed: the tokens whose chosen set of experts differs between
the program's first forward pass and the reference's, by layer (the
program's matmuls round to bfloat16, so a token whose eighth and ninth
experts are nearly tied may choose the other); and the reference with
each of :data:`reference.mellum_moe.FAULTS` planted in its layers, put
in the program's place: every one has to fail a limit of the cell.
"""
import argparse
import json
import sys

import numpy as np


def say(text):
    print(text, flush=True)


def program_choices(program, tokens):
    """The chosen experts of every layer, (layers, tokens, k), in one
    forward pass of the program's block on the trainer's weights: the
    router's output is picked up where the layer computes it."""
    import jax
    from mxnet_tpu.ops import moe
    from mxnet_tpu.parallel.functional import functionalize
    t = program.trainer
    apply_fn, _params = functionalize(t.block, tokens)
    seen, route = [], moe.moe_topk_route

    def spy(x, gate_weight, **kw):
        weights, ids = route(x, gate_weight, **kw)
        seen.append(ids)
        return weights, ids

    def forward(params, tokens):
        del seen[:]
        apply_fn(params, tokens)
        return tuple(seen)

    moe.moe_topk_route = spy
    try:
        return np.stack(jax.device_get(jax.jit(forward)(t.params, tokens)))
    finally:
        moe.moe_topk_route = route


def main(argv=None):
    ap = argparse.ArgumentParser(prog="perfbench.control_mellum")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_300_000_003)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    import jax
    from mxnet_tpu import compile_cache
    from . import check, harness, traffic
    from .adapters import mellum_moe as adapter
    from .reference import mellum_moe as ref
    compile_cache.enable_jax_persistent_cache()
    cell, cfg, mix = harness.load_cell(args.workload)
    devices = jax.devices()[:cell["chips"]]
    if devices[0].platform != "tpu" and not args.rehearsal:
        say("perfbench.control_mellum: no TPU")
        return 2
    dims = cfg["toy"] if args.rehearsal else cfg["dims"]
    mix = mix["toy"] if args.rehearsal else mix
    limits = cell["toy_limits" if args.rehearsal else "limits"]
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    batches = {s: traffic.mlm_batches(mix, dims["vocab_size"], s)[:3]
               for s in seeds}
    program = adapter.build(dict(cfg, use_flash=True), dims,
                            batches[seeds[0]][0], devices,
                            mix.get("mesh", {}))
    chosen = {}
    for s in seeds:
        program.load_weights(ref.init_weights(dims, s))
        chosen[s] = program_choices(program, batches[s][0][0])
    program.free()
    del program
    sizes = ref.leaf_sizes(dims)
    rows = []
    for s in seeds:
        want_ids = np.asarray(ref.chosen_experts(dims, s, batches[s][0][0]))
        differ = (np.sort(chosen[s], -1) != np.sort(want_ids, -1)).any(-1)
        row = {"seed": s, "tokens": int(differ.shape[1]),
               "chosen_set_differs_by_layer": differ.sum(-1).tolist()}
        want = ref.train_steps(dims, cfg["optimizer"], s, batches[s],
                               mix["reference_rows"])
        for fault in ref.FAULTS[1:]:
            got = ref.train_steps(dims, cfg["optimizer"], s, batches[s],
                                  mix["reference_rows"], fault=fault)
            numbers, _where = check.train_numbers(got, want, sizes)
            ok, table = check.verdict(
                numbers, {k: v for k, v in limits.items()
                          if k.endswith("_gap")})
            row["fault_" + fault] = {"passes": ok, "checks": table}
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
