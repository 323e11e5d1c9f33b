"""Runtime feature detection (reference: python/mxnet/runtime.py over
src/libinfo.cc compile-time feature bits)."""
from __future__ import annotations

from typing import Dict

__all__ = ["Features", "feature_list"]


class Feature:
    def __init__(self, name: str, enabled: bool):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _detect() -> Dict[str, bool]:
    import jax
    feats = {
        "TPU": any(d.platform != "cpu" for d in jax.devices()),
        "XLA": True,
        "PJRT": True,
        "CUDA": False,          # by design: no CUDA in the build
        "CUDNN": False,
        "MKLDNN": False,
        "OPENCV": False,
        "DIST_KVSTORE": True,   # xla collectives backend
        "INT64_TENSOR_SIZE": True,
        "SIGNAL_HANDLER": True,
        "PALLAS": True,         # compiled on TPU, interpreted on CPU
        "BF16": True,
        "INT8_QUANTIZATION": True,   # ops/quantization.py int8 MXU path
        "NATIVE_IO": False,     # flipped true when the C++ recordio lib loads
    }
    try:
        from .lib import nativelib
        feats["NATIVE_IO"] = nativelib.available()
    except Exception:
        pass
    return feats


class Features(dict):
    """mx.runtime.Features() (reference: runtime.py)."""

    def __init__(self):
        super().__init__({k: Feature(k, v) for k, v in _detect().items()})

    def is_enabled(self, name: str) -> bool:
        f = self.get(name)
        return bool(f and f.enabled)


def feature_list():
    return list(Features().values())
