"""Chip bring-up guarantees, checked on the CPU (PR 21).

- the device is never silently replaced by the host: a failing backend
  raises, and `--device-prepass always` exits non-zero when the
  prepass fails;
- the chip smoke refuses to pass without a TPU;
- no child process reaches for the chip its parent holds, and importing
  the engine initializes no JAX backend;
- the compile cache and the native library live inside the checkout
  and are found again.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: SSTORE(0, 1) only when calldata byte 0 == 0x42 (long enough for the
#: per-contract prepass, which skips codes under 4 bytes)
GATED = "60003560f81c604214600d57005b600160005500"
KILLABLE_GATED = "604260003560f81c14600d57005b33ff"


# -- no silent host fallback -------------------------------------------------
def test_accelerator_present_is_false_only_on_cpu(monkeypatch):
    import jax

    from mythril_tpu.support.accel import accelerator_present

    assert accelerator_present() is False

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        accelerator_present()


@pytest.mark.parametrize(
    "codes", [[GATED], [GATED, KILLABLE_GATED]], ids=["contract", "corpus"]
)
def test_prepass_always_fault_exits_nonzero(
    monkeypatch, capsys, tmp_path, codes
):
    """An armed wave fault fails the device prepass: under
    `--device-prepass always` `myth analyze` exits 1 (single contract:
    the per-contract prepass; several: the overlapped corpus prepass)."""
    from mythril_tpu.interfaces.cli import main
    from mythril_tpu.laser.ethereum.time_handler import time_handler
    from mythril_tpu.support import resilience
    from mythril_tpu.support.support_args import args

    # the aborted run leaves its execution budget armed in the
    # process-wide time handler: restore it for later tests
    monkeypatch.setattr(time_handler, "start_time", time_handler.start_time)
    monkeypatch.setattr(
        time_handler, "execution_time", time_handler.execution_time
    )
    paths = []
    for i, code in enumerate(codes):
        path = tmp_path / f"c{i}.hex"
        path.write_text(code + "\n")
        paths.append(str(path))
    monkeypatch.setattr(
        sys,
        "argv",
        [
            "myth", "analyze", "-f", *paths, "--bin-runtime",
            "--no-onchain-data", "-t", "1", "--execution-timeout", "30",
            "-o", "json", "--device-prepass", "always",
        ],
    )
    # main() parses into the process-wide args (device_first, solver
    # budgets...): later tests in this process must find them as set
    saved = dict(vars(args))
    resilience.arm_fault("explore.wave", times=99)
    try:
        with pytest.raises(SystemExit) as exit_:
            main()
        assert resilience.fault_fire_count("explore.wave") >= 1
    finally:
        resilience.disarm_faults()
        vars(args).clear()
        vars(args).update(saved)
    assert exit_.value.code == 1
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["success"] is False
    assert "prepass failed" in report["error"]


def test_chip_smoke_fails_without_a_tpu(tmp_path):
    """On the CPU backend, and in a directory holding only the script,
    the chip smoke exits non-zero and never prints its verdict."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), alone)
    for script in (os.path.join(REPO, "chip_smoke.py"), str(alone)):
        out = subprocess.run(
            [sys.executable, script],
            capture_output=True,
            text=True,
            timeout=240,
            env=env,
            cwd=os.path.dirname(script),
        )
        assert out.returncode != 0, out.stdout[-2000:]
        assert '"ok": true' not in out.stdout
    assert "no TPU" in subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO,
    ).stdout


def test_graft_multichip_never_swaps_an_accelerator_for_cpu(monkeypatch):
    import jax

    from __graft_entry__ import _ensure_devices

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="devices wanted"):
        _ensure_devices(len(jax.devices()) + 1)


# -- one process per chip ----------------------------------------------------
def _child_platform(_):
    return os.environ.get("JAX_PLATFORMS")


def _child_state(_):
    import jax

    return os.getpid(), os.environ.get("JAX_PLATFORMS"), jax.devices()[0].platform


def _crash(_):
    os._exit(1)


def test_corpus_workers_start_pinned_to_cpu(monkeypatch):
    from mythril_tpu.analysis.corpus import _host_worker_pool

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with _host_worker_pool(1) as pool:
        assert pool.map(_child_platform, [0]) == ["cpu"]
        assert "JAX_PLATFORMS" not in os.environ
    assert "JAX_PLATFORMS" not in os.environ


def test_respawned_corpus_worker_stays_on_cpu(monkeypatch):
    """A worker that dies is replaced; the replacement starts pinned to
    the CPU too, with the parent's environment left as it was."""
    from mythril_tpu.analysis.corpus import _host_worker_pool

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with _host_worker_pool(1) as pool:
        first = pool.apply(_child_state, (0,))
        pool.apply_async(_crash, (0,))
        second = pool.apply(_child_state, (0,))
    assert first[0] != second[0]
    assert first[1:] == second[1:] == ("cpu", "cpu")


# -- the device solver funnel ------------------------------------------------
def _funnel_queries(n):
    from mythril_tpu.laser.smt import terms

    # wide equalities SLS does not solve in a few steps
    return [
        [terms.eq(
            terms.mul(terms.bv_var(f"bu_q{i}", 64), terms.bv_const(3, 64)),
            terms.bv_const(0x5A5A5A5A5A5A5A5B + 2 * i, 64),
        )]
        for i in range(n)
    ]


def test_batched_search_keeps_the_opcode_switch():
    """The batched search maps over its rows: each row's opcode stays a
    scalar and the interpreter's switch stays a conditional (vmapped,
    every branch ran at every node and the funnel ran for minutes on
    the chip)."""
    import jax
    import jax.numpy as jnp

    from mythril_tpu.laser.smt.solver import portfolio

    live = [
        (i, portfolio.compile_program(q))
        for i, q in enumerate(_funnel_queries(2))
    ]
    rows = portfolio.SEARCH_ROWS
    args, limbs = portfolio._stacked_args(live, rows)
    search = portfolio._get_search_fn(4, limbs, 2)
    args += (jnp.arange(rows, dtype=jnp.int32), jnp.arange(rows) < 2)
    jaxpr = jax.make_jaxpr(portfolio._search_rows(search.raw))(*args)

    def branch_counts(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "cond":
                yield len(eqn.params["branches"])
            for param in eqn.params.values():
                for sub in param if isinstance(param, tuple) else (param,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from branch_counts(sub)

    assert len(portfolio.OPS) in set(branch_counts(jaxpr.jaxpr))


def test_funnel_stops_between_dispatches(monkeypatch):
    """An owner's stop is honored between search dispatches: a batch
    past SEARCH_ROWS queries stops after the dispatch in flight."""
    from mythril_tpu.laser.smt.solver import portfolio

    dispatched = []
    stack = portfolio._stacked_args

    def counted(live, rows):
        dispatched.append(len(live))
        return stack(live, rows)

    monkeypatch.setattr(portfolio, "_stacked_args", counted)
    n = portfolio.SEARCH_ROWS + 3
    verdicts = portfolio.device_solve_batch(
        _funnel_queries(n), candidates=4, steps=2,
        should_stop=lambda: bool(dispatched),
    )
    assert dispatched == [portfolio.SEARCH_ROWS]
    assert len(verdicts) == n


def test_cli_waits_for_a_stopped_prepass(monkeypatch):
    """A prepass thread finish() left inside a device call is joined
    before the CLI returns: none is alive when the interpreter exits."""
    import threading

    from mythril_tpu.analysis import corpus
    from mythril_tpu.interfaces.cli import main

    release = threading.Event()
    thread = threading.Thread(
        target=release.wait, name=corpus.PREPASS_THREAD, daemon=True
    )
    thread.start()
    monkeypatch.setattr(corpus, "_STOPPED_PREPASSES", [thread])
    monkeypatch.setattr(sys, "argv", ["myth", "version"])
    threading.Timer(0.5, release.set).start()
    with pytest.raises(SystemExit):
        main()
    assert not thread.is_alive()
    assert corpus._STOPPED_PREPASSES == []


def test_importing_the_engine_initializes_no_backend():
    probe = (
        "import jax._src.xla_bridge as xb;"
        "import mythril_tpu.interfaces.cli, mythril_tpu.analysis.corpus,"
        " mythril_tpu.laser.batch.explore, mythril_tpu.service.engine,"
        " mythril_tpu.laser.smt.solver.portfolio;"
        "print(bool(xb._backends))"
    )
    out = subprocess.run(
        [sys.executable, "-c", probe],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "False"


# -- what lives in the checkout ----------------------------------------------
def test_compile_cache_location_rule(monkeypatch, tmp_path):
    import jax

    from mythril_tpu.support.accel import ensure_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", "untouched")
        assert ensure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == "untouched"

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        inside = os.path.join(REPO, ".jax_cache")
        assert ensure_compile_cache() == inside
        assert jax.config.jax_compilation_cache_dir == inside
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == inside
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_native_library_builds_from_its_sources(monkeypatch, tmp_path):
    from mythril_tpu import native

    src = os.path.dirname(native.__file__)
    for name in os.listdir(src):
        if name.endswith(".cpp") or name == "Makefile":
            shutil.copy(os.path.join(src, name), tmp_path / name)
    monkeypatch.setattr(native, "_DIR", tmp_path)
    monkeypatch.setattr(native, "LIBRARY", tmp_path / "libmythril_native.so")
    built = native.library_path()
    assert os.path.isfile(built)
    mtime = os.path.getmtime(built)
    assert native.library_path() == built
    assert os.path.getmtime(built) == mtime  # fresh: not rebuilt
    # a source newer than the library rebuilds it
    os.utime(tmp_path / "keccak.cpp", (mtime + 10, mtime + 10))
    native.library_path()
    assert os.path.getmtime(built) > mtime
    import ctypes

    assert hasattr(ctypes.CDLL(built), "mtpu_keccak256")
