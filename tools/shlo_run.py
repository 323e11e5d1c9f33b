"""Run an exported StableHLO artifact through the C++ PJRT loader.

The loader (``mxnet_tpu/lib/shlo_runner``, built by
``ci/runtime_functions.sh native_build``) is a dependency-free binary:
it dlopens a PJRT C-API plugin, compiles the MLIR module from
``deploy.export_stablehlo(..., emit_text=True)`` and executes it —
proving the deployment artifact is language-neutral
(docs/frontends.md §2; reference: cpp-package consumes the C ABI).

This wrapper supplies the serialized default CompileOptions and names
the plugin: ``--plugin`` or ``MXNET_TEST_PJRT_PLUGIN`` is required (a
generic plugin, e.g. a CPU PJRT plugin .so, needs no client-create
options).

Usage:
  python tools/shlo_run.py <module.mlir> <out_prefix> \
      dtype@d0xd1@input.bin [...] [--plugin /path/plugin.so]
"""
import argparse
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUNNER = os.path.join(REPO, "mxnet_tpu", "lib", "shlo_runner")


def run(module, out_prefix, inputs, plugin=None, check=True):
    plugin = plugin or os.environ.get("MXNET_TEST_PJRT_PLUGIN")
    if not plugin:
        raise ValueError("no PJRT plugin: pass --plugin or set "
                         "MXNET_TEST_PJRT_PLUGIN")
    if not os.path.exists(RUNNER):
        raise FileNotFoundError(
            f"{RUNNER} not built — run ci/runtime_functions.sh "
            f"native_build")
    # serialized default CompileOptions (plugins generally require one)
    from jaxlib._jax import CompileOptions
    with tempfile.NamedTemporaryFile(suffix=".pb", delete=False) as f:
        f.write(CompileOptions().SerializeAsString())
        opts_path = f.name
    argv = [RUNNER, plugin, module, opts_path, out_prefix] + list(inputs)
    try:
        return subprocess.run(argv, check=check,
                              capture_output=True, text=True)
    finally:
        os.unlink(opts_path)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("module")
    ap.add_argument("out_prefix")
    ap.add_argument("inputs", nargs="*",
                    help="dtype@d0xd1@file.bin per input")
    ap.add_argument("--plugin", default=None)
    a = ap.parse_args()
    proc = run(a.module, a.out_prefix, a.inputs, a.plugin, check=False)
    sys.stderr.write(proc.stderr)
    return proc.returncode


if __name__ == "__main__":
    sys.exit(main())
