"""What the runners share: finding a cell's files by name, the profiler
switch, percentiles, the device's description."""
import importlib
import json
import os
import shutil

ROOT = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(ROOT)
OUT_DIR = os.path.join(REPO, ".perfbench_out")       # git-ignored


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_cell(name):
    """(workload, config, traffic mix) of the cell ``name``, each from
    the file that carries its name."""
    cell = load_json("workloads", name + ".json")
    cfg = load_json("configs", cell["config"] + ".json")
    mix = load_json("mixes", cell["traffic"] + ".json")
    return cell, cfg, mix


def cell_metrics(cell_name, end_to_end):
    """The per-layer metric files that apply to a cell: those that list
    it under ``workloads``, and those without that key whose ``moves``
    the cell reports."""
    out = []
    folder = os.path.join(ROOT, "metrics")
    for fn in sorted(os.listdir(folder)):
        if not fn.endswith(".json"):
            continue
        m = load_json("metrics", fn)
        if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in end_to_end):
            out.append(m)
    return out


def end_to_end_units(names):
    """(name, unit) of a runner's end-to-end metrics and ``setup_s``,
    as ``BENCHMARK.json`` declares them."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        declared = {m["name"]: m["unit"] for m in json.load(f)["end_to_end"]}
    return [(n, declared[n]) for n in tuple(names) + ("setup_s",)]


def module(kind, name):
    return importlib.import_module(f"perfbench.{kind}.{name}")


def percentile(values, q):
    """The q-th percentile by the nearest-rank rule (no interpolation:
    a tail is a request that happened)."""
    xs = sorted(values)
    rank = max(1, -(-len(xs) * q // 100))
    return xs[int(rank) - 1]


def device_info(devices):
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class Profiler:
    """The JAX profiler around one traced window, written under the
    checkout.  Python-level tracing is off: the benchmark's own
    ``pb.*`` annotations and the device's lines are what is read."""

    def __init__(self, cell_name):
        self.dir = os.path.join(OUT_DIR, "trace", cell_name)

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def load(self):
        from . import trace_reduce
        return trace_reduce.load(trace_reduce.find_xplane(self.dir))
