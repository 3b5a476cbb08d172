"""Prebaked kernel packs: hot buckets compiled ahead of time.

A pack is an artifact-cache directory (cache.py layout) plus a
``pack.json`` manifest recording the backend fingerprint, the dispatch
shape it was baked for, and the bucket list. `myth kernels bake`
produces one; `myth serve --kernel-pack DIR` mounts it at boot
(plane.mount_packs) so a fresh replica — an autoscale-up, a failover
restart — reaches readiness without a single in-process compile of a
packed bucket.

Baking reuses the EXACT dispatch path the service runs: the bucket's
kernel is invoked once over a zero arena of the service's dispatch
shape with the plane's cache directory pointed at the pack, so the
write-back wiring in specialize.py/run.py produces the artifact. That
guarantees the baked entry digest matches what the replica computes
at load time — there is no second shape-derivation to drift.

Bucket mining: an explicit bucket-list JSON, a capture corpus (each
contract's signature -> PhaseSet, the per-code path), and/or routing
JSONL rows carrying the full ``phase_bucket`` feature. The engine
dispatches the MONOTONE UNION bucket of resident jobs, so the bake
always adds the running union of the mined buckets (and the generic
kernel) alongside the per-contract buckets.
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from mythril_tpu.compileplane.cache import ArtifactCache
from mythril_tpu.compileplane.keys import bucket_key, phases_from_bucket
from mythril_tpu.compileplane.plane import (
    CompilePlane,
    install_plane,
)

log = logging.getLogger(__name__)

PACK_MANIFEST = "pack.json"
PACK_SCHEMA_VERSION = 1


# ---------------------------------------------------------------------------
# bucket mining
# ---------------------------------------------------------------------------
def _iter_code_files(paths: Sequence[str]) -> Iterable[Tuple[str, bytes]]:
    """(path, code bytes) for every contract file under `paths` —
    hex text (0x-prefixed or bare) or raw EVM bytes."""
    files: List[str] = []
    for path in paths:
        if os.path.isdir(path):
            for name in sorted(os.listdir(path)):
                full = os.path.join(path, name)
                if os.path.isfile(full):
                    files.append(full)
        elif os.path.isfile(path):
            files.append(path)
    for full in files:
        try:
            with open(full, "rb") as fp:
                raw = fp.read()
        except OSError:
            continue
        text = raw.strip()
        if text[:2] in (b"0x", b"0X"):
            text = text[2:]
        try:
            code = bytes.fromhex(text.decode("ascii"))
        except (ValueError, UnicodeDecodeError):
            code = raw
        if code:
            yield full, code


def _phases_for_code(code: bytes, blockjit: bool = True):
    """One contract's specialization bucket, the per-code admission
    path in miniature (static summary when available, byte sweep
    otherwise)."""
    from mythril_tpu.laser.batch import specialize as _spec

    summary = None
    try:
        from mythril_tpu.analysis.static import (
            static_prune_enabled,
            summary_for,
        )

        if static_prune_enabled():
            summary = summary_for(code.hex())
    except Exception:
        summary = None
    block_depth = 0
    if blockjit:
        try:
            from mythril_tpu.laser.batch.blockjit import (
                block_depth_for,
                blockjit_enabled,
            )

            if blockjit_enabled():
                block_depth = block_depth_for(code, summary)
        except Exception:
            block_depth = 0
    return _spec.phases_for(
        _spec.signature_for(code, summary),
        fuse=_spec.fuse_profitable(code, summary),
        block_depth=block_depth,
    )


def mine_buckets(
    corpus: Sequence[str] = (),
    routing: Sequence[str] = (),
    bucket_files: Sequence[str] = (),
    blockjit: bool = True,
    include_generic: bool = True,
    include_union: bool = True,
) -> List[Optional[object]]:
    """The deduplicated bucket list to bake: None means the generic
    kernel; everything else is a step.PhaseSet."""
    from mythril_tpu.laser.batch import specialize as _spec

    seen: Dict[str, Optional[object]] = {}

    def add(phases) -> None:
        key = json.dumps(bucket_key(phases), sort_keys=True)
        seen.setdefault(key, phases)

    if include_generic:
        add(None)
    for path in bucket_files:
        with open(path) as fp:
            data = json.load(fp)
        buckets = data.get("buckets") if isinstance(data, dict) else data
        for bucket in buckets or []:
            add(phases_from_bucket(bucket))
    for _path, code in _iter_code_files(corpus):
        try:
            add(_phases_for_code(code, blockjit=blockjit))
        except Exception:
            log.debug("bucket mining failed for %s", _path, exc_info=True)
    for path in routing:
        try:
            with open(path) as fp:
                lines = fp.readlines()
        except OSError:
            continue
        for line in lines:
            try:
                row = json.loads(line)
            except ValueError:
                continue
            bucket = (row.get("features") or {}).get("phase_bucket")
            if isinstance(bucket, dict):
                add(phases_from_bucket(bucket))
    mined = [p for p in seen.values() if p is not None]
    if include_union and mined:
        # the engine dispatches the monotone union of resident
        # buckets: multi-contract residency hits THIS entry, not the
        # per-contract ones
        add(_spec.union_phases(mined))
    return list(seen.values())


# ---------------------------------------------------------------------------
# the service dispatch shape
# ---------------------------------------------------------------------------
def service_shape(
    stripes: int,
    lanes_per_stripe: int,
    steps_per_wave: int,
    code_cap: int = 2048,
) -> Dict:
    """The dispatch-shape record a bake targets — the same derivation
    `service/engine.py` applies at boot (code_cap_bucket floor, the
    +1 halt row, the default batch capacities)."""
    from mythril_tpu.laser.batch.seeds import code_cap_bucket
    from mythril_tpu.laser.batch.state import (
        CALLDATA_CAP,
        MEM_CAP,
        STACK_CAP,
    )

    cap = code_cap_bucket(1, floor=int(code_cap))
    return {
        "stripes": int(stripes),
        "lanes_per_stripe": int(lanes_per_stripe),
        "n_lanes": int(stripes) * int(lanes_per_stripe),
        "steps_per_wave": int(steps_per_wave),
        "code_cap": cap,
        "rows": int(stripes) + 1,
        "mem_cap": MEM_CAP,
        "stack_cap": STACK_CAP,
        "calldata_cap": CALLDATA_CAP,
    }


def _arena_for(shape: Dict):
    """(batch, table, substep_table) of the exact dispatch avals the
    serving engine produces — the kernels are value-independent, so a
    zero arena compiles the same executable the live arena runs."""
    import jax.numpy as jnp

    from mythril_tpu.laser.batch.state import CodeTable, make_batch

    rows, cap = shape["rows"], shape["code_cap"]
    table = CodeTable(
        jnp.asarray(np.zeros((rows, cap + 33), np.uint8)),
        jnp.asarray(np.zeros((rows, cap), bool)),
        jnp.asarray(np.zeros((rows,), np.int32)),
    )
    substep = jnp.asarray(np.zeros((rows, cap), np.uint8))
    n = shape["n_lanes"]
    batch = make_batch(
        n,
        code_ids=np.full((n,), shape["stripes"], np.int32),
        calldata=[b""] * n,
    )
    return batch, table, substep


# ---------------------------------------------------------------------------
# baking
# ---------------------------------------------------------------------------
def bake_service_pack(
    out_dir: str,
    buckets: Sequence[Optional[object]],
    stripes: int,
    lanes_per_stripe: int,
    steps_per_wave: int,
    code_cap: int = 2048,
    donate_variants: Optional[Sequence[bool]] = None,
    progress=None,
) -> Dict:
    """Compile every bucket for the service dispatch shape into
    `out_dir` and write the manifest. Idempotent: an artifact already
    present (and loadable) is reused, not recompiled — re-baking a
    pack is a cheap verification pass."""
    import jax

    from mythril_tpu.laser.batch.run import wave_run
    from mythril_tpu.laser.batch.specialize import SpecializedKernel

    shape = service_shape(
        stripes, lanes_per_stripe, steps_per_wave, code_cap
    )
    if donate_variants is None:
        # the variants the serve path dispatches: warmup runs
        # undonated; real waves donate off-CPU
        donate_variants = (
            (False, True) if jax.default_backend() != "cpu" else (False,)
        )
    plane = CompilePlane(cache_dir=out_dir, capacity=1 << 30)
    previous = install_plane(plane)
    baked: List[Dict] = []
    # bake with jax's persistent XLA compilation cache OFF: an
    # executable XLA:CPU loads from that cache serializes into a stub
    # missing its function symbols, so a bake riding it would produce
    # artifacts every consumer refuses (the store's trial roundtrip
    # catches them, but then the pack comes out empty) — pay the fresh
    # compile, it is the whole point of the bake
    prev_cc_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    # clearing the dir is not enough on its own: jax latches the
    # cache-used decision at the first compile of the process, so reset
    # the latch so the dir=None takes effect (and again afterwards so
    # post-bake compiles re-latch against the restored dir). The
    # in-memory compile memo must go too: it would hand the bake an
    # executable an earlier compile loaded from the persistent cache.
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()
    jax.clear_caches()
    try:
        for phases in buckets:
            for donate in donate_variants:
                batch, table, substep = _arena_for(shape)
                t0 = time.perf_counter()
                if phases is None:
                    out = wave_run(
                        batch,
                        table,
                        max_steps=shape["steps_per_wave"],
                        track_coverage=True,
                        donate=donate,
                    )
                else:
                    kernel = SpecializedKernel(phases)
                    out = kernel.run(
                        batch,
                        table,
                        substep,
                        max_steps=shape["steps_per_wave"],
                        track_coverage=True,
                        donate=donate,
                    )
                jax.block_until_ready(out[1])
                row = {
                    "bucket": bucket_key(phases),
                    "donate": donate,
                    "wall_s": round(time.perf_counter() - t0, 3),
                }
                baked.append(row)
                if progress is not None:
                    progress(row)
        manifest = {
            "schema_version": PACK_SCHEMA_VERSION,
            "created_at": time.time(),
            "fingerprint": plane.fingerprint,
            "fingerprint_hex": plane.fp_hex,
            "shape": shape,
            "buckets": [bucket_key(p) for p in buckets],
            "baked": baked,
            "artifacts": len(plane.cache),
            "plane": {
                "pack_hits": plane.pack_hits,
                "cache_hits": plane.cache_hits,
                "misses": plane.misses,
                "stores": plane.stores,
                "unsupported": dict(plane.unsupported),
            },
        }
        _write_manifest(out_dir, manifest)
        return manifest
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_cc_dir)
        compilation_cache.reset_cache()
        install_plane(previous)


def _write_manifest(pack_dir: str, manifest: Dict) -> None:
    path = os.path.join(pack_dir, PACK_MANIFEST)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fp:
        json.dump(manifest, fp, sort_keys=True, indent=2)
        fp.flush()
        os.fsync(fp.fileno())
    os.replace(tmp, path)


# ---------------------------------------------------------------------------
# introspection / maintenance (myth kernels ls|warm|gc)
# ---------------------------------------------------------------------------
def read_manifest(pack_dir: str) -> Optional[Dict]:
    try:
        with open(os.path.join(pack_dir, PACK_MANIFEST)) as fp:
            data = json.load(fp)
        return data if isinstance(data, dict) else None
    except (OSError, ValueError):
        return None


def list_pack(pack_dir: str) -> Dict:
    """Manifest + per-artifact headers for `myth kernels ls`."""
    cache = ArtifactCache(pack_dir, capacity=1 << 30)
    headers = cache.headers()
    return {
        "dir": os.path.abspath(pack_dir),
        "manifest": read_manifest(pack_dir),
        "artifacts": [
            {
                "key": h.get("key"),
                "bucket": h.get("bucket"),
                "entry": h.get("entry"),
                "fingerprint_hex": h.get("fingerprint_hex"),
                "blob_len": h.get("blob_len"),
            }
            for h in headers
        ],
    }


def verify_pack(pack_dir: str) -> Dict:
    """Load every artifact under the CURRENT backend fingerprint —
    `myth kernels warm`: the preflight a deploy runs before pointing
    replicas at a pack."""
    from mythril_tpu.compileplane import aot
    from mythril_tpu.compileplane.fingerprint import fingerprint_hex

    cache = ArtifactCache(pack_dir, capacity=1 << 30)
    fp_hex = fingerprint_hex()
    ok = refused = 0
    reasons: Dict[str, int] = {}
    for key in cache.keys():
        got = cache.read(key, expected_fp=fp_hex)
        if got is None:
            refused += 1
            reasons["refused"] = reasons.get("refused", 0) + 1
            continue
        try:
            aot.load_serialized(got[1])
            ok += 1
        except aot.AotUnsupported as why:
            refused += 1
            reasons[why.reason] = reasons.get(why.reason, 0) + 1
    return {
        "dir": os.path.abspath(pack_dir),
        "fingerprint_hex": fp_hex,
        "loadable": ok,
        "refused": refused,
        "reasons": reasons,
    }


def gc_pack(
    pack_dir: str, capacity: int, drop_stale: bool = False
) -> Dict:
    """LRU-trim a pack/cache directory to `capacity` artifacts; with
    `drop_stale`, also unlink artifacts whose header fingerprint does
    not match this backend (a toolchain upgrade orphans them)."""
    from mythril_tpu.compileplane.fingerprint import fingerprint_hex

    cache = ArtifactCache(pack_dir, capacity=max(1, int(capacity)))
    stale = 0
    if drop_stale:
        fp_hex = fingerprint_hex()
        for header in cache.headers():
            if header.get("fingerprint_hex") != fp_hex:
                try:
                    os.unlink(
                        os.path.join(
                            cache.artifacts_dir, f"{header['key']}.aotx"
                        )
                    )
                    stale += 1
                except (OSError, KeyError):
                    continue
    evicted = cache.evict()
    return {
        "dir": os.path.abspath(pack_dir),
        "stale_dropped": stale,
        "evicted": evicted,
        "remaining": len(cache),
    }
