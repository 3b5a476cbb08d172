"""The backend fingerprint: what makes an XLA executable reusable.

A serialized executable is only valid on the toolchain and device
family that produced it — jax/jaxlib version bumps change the
serialization format, a different device kind changes the lowered
code, and XLA flags change codegen. The fingerprint covers all of
them; it is part of every artifact's content-addressed key AND
repeated inside the artifact header, so a stale artifact is refused
twice over (wrong filename, then wrong header) rather than mis-loaded
— the DTVM determinism-fingerprint discipline the verdict store
already applies to verdicts (store/store.py).
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Dict, Optional

_FP: Optional[Dict] = None
_FP_MU = threading.Lock()


def backend_fingerprint() -> Dict:
    """The current process's backend identity, as a flat JSON-able
    dict. Computed once per process (it initializes the JAX backend)."""
    global _FP
    with _FP_MU:
        if _FP is not None:
            return dict(_FP)
        import jax
        import jaxlib

        from mythril_tpu.ops import u256

        _FP = {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "xla_flags": os.environ.get("XLA_FLAGS", ""),
            "limbs": int(u256.LIMBS),
        }
        return dict(_FP)


def fingerprint_hex(fp: Optional[Dict] = None) -> str:
    """The fingerprint's canonical hex digest (artifact-key
    component)."""
    if fp is None:
        fp = backend_fingerprint()
    return hashlib.sha256(
        json.dumps(fp, sort_keys=True).encode()
    ).hexdigest()[:16]


def reset_fingerprint() -> None:
    """Test hook: recompute on next use (e.g. after monkeypatching
    XLA_FLAGS)."""
    global _FP
    with _FP_MU:
        _FP = None
