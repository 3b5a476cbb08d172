"""`observe.trace()` spans on the JAX profiler's clock (tier-1
`observe` marker).

Each span is also a profiler annotation once the process has imported
jax, so under `jax.profiler.trace` it lands on a host plane of the
trace beside the device's ops. Pins the name, the duration against the
ring span's, nesting on the trace's clock, that a disabled observe
layer emits into neither, and that observe itself never imports jax.
CPU-only."""

import subprocess
import sys
import time

import pytest

from mythril_tpu import observe
from mythril_tpu.observe.spans import flight_recorder

pytestmark = pytest.mark.observe


def _host_events(logdir):
    """name -> [(start_ns, duration_ns)] over the trace's host planes."""
    from jax.profiler import ProfileData

    found = sorted(logdir.glob("plugins/profile/*/*.xplane.pb"))
    assert found, "the profiler wrote no trace"
    data = ProfileData.from_file(str(found[-1]))
    out = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for event in line.events:
                out.setdefault(event.name, []).append(
                    (event.start_ns, event.duration_ns)
                )
    return out


def _ring_span(name):
    spans = [s for s in flight_recorder().tail(4096) if s.name == name]
    return spans[-1] if spans else None


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    import jax
    import jax.numpy as jnp

    was = observe.enabled()
    observe.set_enabled(True)
    try:
        with jax.profiler.trace(str(tmp_path)):
            jnp.ones(8).block_until_ready()
            with observe.trace("test.profiler.outer", track="t", job="j1"):
                time.sleep(0.02)
                with observe.trace("test.profiler.inner"):
                    time.sleep(0.01)
            observe.set_enabled(False)
            with observe.trace("test.profiler.disabled"):
                time.sleep(0.005)
            observe.set_enabled(True)
    finally:
        observe.set_enabled(was)
    events = _host_events(tmp_path)
    outer, inner = _ring_span("test.profiler.outer"), _ring_span("test.profiler.inner")
    assert outer is not None and inner is not None
    assert inner.parent == outer.sid
    (o_start, o_dur), = events["test.profiler.outer"]
    (i_start, i_dur), = events["test.profiler.inner"]
    # the same span on both clocks: durations agree within 2 ms
    assert abs(o_dur * 1e-9 - (outer.t1 - outer.t0)) < 2e-3
    assert abs(i_dur * 1e-9 - (inner.t1 - inner.t0)) < 2e-3
    # the child lies inside its parent on the trace's clock
    assert o_start <= i_start
    assert i_start + i_dur <= o_start + o_dur
    # disabled: in neither the ring nor the trace
    assert _ring_span("test.profiler.disabled") is None
    assert "test.profiler.disabled" not in events


def test_observe_never_imports_jax():
    code = (
        "import sys\n"
        "from mythril_tpu import observe\n"
        "with observe.trace('x', job='a'):\n"
        "    pass\n"
        "assert len(observe.flight_recorder()) == 1\n"
        "print('jax' in sys.modules)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, check=True,
    )
    assert out.stdout.strip() == "False"
