"""The C++ PJRT loader consumes exported StableHLO artifacts with NO
framework and NO Python — the frontends/deployment claim proven
language-neutral (docs/frontends.md §2; VERDICT r3 stretch item).

Opt-in: needs a PJRT plugin .so and possibly the accelerator it talks
to, so it only runs when MXNET_TEST_PJRT_PLUGIN is set (the
`native_build` CI job does this where a plugin is available).  A TPU
plugin runs on the real chip, which one process holds at a time — it
must not race a live benchmark run.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, gluon, deploy
from mxnet_tpu.gluon import nn

pytestmark = pytest.mark.skipif(
    not os.environ.get("MXNET_TEST_PJRT_PLUGIN"),
    reason="set MXNET_TEST_PJRT_PLUGIN=/path/plugin.so to run the "
           "framework-free PJRT loader end-to-end")


def test_cpp_loader_matches_python(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import shlo_run

    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, activation="relu"), nn.MaxPool2D(),
            nn.Flatten(), nn.Dense(10))
    net.initialize(mx.init.Xavier())
    x = nd.array(np.random.RandomState(0).randn(2, 1, 28, 28)
                 .astype(np.float32))
    net(x)
    prefix = str(tmp_path / "lenet")
    deploy.export_stablehlo(net, x, path=prefix, emit_text=True)
    ref = net(x).asnumpy()
    xbin = str(tmp_path / "x.bin")
    x.asnumpy().tofile(xbin)

    proc = shlo_run.run(prefix + ".stablehlo.txt",
                        str(tmp_path / "out"),
                        [f"f32@2x1x28x28@{xbin}"], check=False)
    assert proc.returncode == 0, proc.stderr
    meta = open(str(tmp_path / "out.0.meta")).read().split()
    assert meta[0] == "f32" and meta[1:] == ["2", "10"], meta
    out = np.fromfile(str(tmp_path / "out.0.bin"),
                      np.float32).reshape(2, 10)
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)
