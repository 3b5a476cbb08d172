"""JAX AOT executable export/import, with an honest failure taxonomy.

`jit.lower(...).compile()` produces a Compiled object whose underlying
XLA executable `jax.experimental.serialize_executable` can flatten to
bytes (plus the in/out pytree defs, which pickle — the batch pytrees
are NamedTuples). A deserialized executable is invoked with the
DYNAMIC arguments only: the statics it was lowered with are baked in.

Two facts shape every call site:

- `.lower().compile()` does NOT populate the jit object's dispatch
  cache — an AOT-compiled or loaded executable must be dispatched
  through its own handle, never by re-calling the jit object (which
  would silently recompile).
- not every backend supports executable serialization. Every distinct
  failure mode raises `AotUnsupported` with a stable `reason` string,
  and callers degrade to the in-process jit compile — CPU-only tier-1
  behaves exactly as before this layer existed, with the reason
  attributed in `mtpu_compileplane_unsupported_total`.
"""

from __future__ import annotations

import io
import os
import pickle

#: AOT_UNSUPPORTED reasons (stable label vocabulary)
REASON_DISABLED = "disabled"  # --no-aot / MYTHRIL_NO_AOT
REASON_SERIALIZE = "serialize-failed"
REASON_DESERIALIZE = "deserialize-failed"
REASON_LOWER = "lower-failed"  # .lower()/.compile() itself


class AotUnsupported(RuntimeError):
    """AOT export/import is unavailable for this attempt; `reason` is
    one of the REASON_* labels, `detail` the underlying error."""

    def __init__(self, reason: str, detail: str = "") -> None:
        self.reason = reason
        self.detail = detail
        super().__init__(
            f"AOT unsupported ({reason})" + (f": {detail}" if detail else "")
        )


def aot_enabled() -> bool:
    """The AOT layer switch: env MYTHRIL_NO_AOT (read live, so tests
    can flip it per-case) AND the support_args flag (CLI --no-aot)."""
    if os.environ.get("MYTHRIL_NO_AOT"):
        return False
    from mythril_tpu.support.support_args import args

    return bool(getattr(args, "aot", True))


def serialize_compiled(compiled) -> bytes:
    """Compiled (from `jit.lower().compile()`) -> portable bytes:
    pickle of the (payload, in_tree, out_tree) triple
    serialize_executable.serialize returns, plus the ids of the devices
    the executable runs on. `deserialize_and_load` otherwise loads onto
    EVERY device of the backend, and a one-device kernel then expects
    one shard per device on a multi-device host."""
    from jax.experimental import serialize_executable as se

    try:
        payload, in_tree, out_tree = se.serialize(compiled)
        device_ids = [
            d.id for d in compiled.runtime_executable().local_devices()
        ]
        buf = io.BytesIO()
        pickle.dump(
            (payload, in_tree, out_tree, device_ids),
            buf,
            protocol=pickle.HIGHEST_PROTOCOL,
        )
        return buf.getvalue()
    except Exception as why:
        raise AotUnsupported(REASON_SERIALIZE, str(why))


def load_serialized(blob: bytes):
    """Portable bytes -> a callable Compiled, invoked with the dynamic
    arguments only. Artifacts come from the operator-owned cache/pack
    directories (same trust domain as the code being analyzed)."""
    import jax
    from jax.experimental import serialize_executable as se

    try:
        payload, in_tree, out_tree, device_ids = pickle.loads(blob)
        by_id = {d.id: d for d in jax.local_devices()}
        return se.deserialize_and_load(
            payload,
            in_tree,
            out_tree,
            execution_devices=[by_id[i] for i in device_ids],
        )
    except Exception as why:
        raise AotUnsupported(REASON_DESERIALIZE, str(why))
