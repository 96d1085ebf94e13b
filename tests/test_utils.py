"""Stage-0 acceptance: grid functions, timers, metrics, checkpoint round-trip."""

import json
import math
import os
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np
import pytest

from ibamr_tpu.utils.checkpoint import (
    latest_step, restore_checkpoint, save_checkpoint)
from ibamr_tpu.utils.gridfunctions import CartGridFunction
from ibamr_tpu.utils.input_db import parse_input_string
from ibamr_tpu.utils.gridfunctions import function_from_db
from ibamr_tpu.utils.metrics import MetricsLogger
from ibamr_tpu.utils.timers import TimerManager


def test_gridfunction_scalar():
    f = CartGridFunction("sin(2*PI*X_0)*cos(2*PI*X_1)", dim=2)
    x = jnp.array([0.25])
    y = jnp.array([0.0])
    v = f((x, y), t=0.0)
    assert float(v[0]) == pytest.approx(math.sin(math.pi / 2), abs=1e-6)


def test_gridfunction_time_and_power():
    f = CartGridFunction("t + X_0^2", dim=1)
    v = f((jnp.array([3.0]),), t=1.5)
    assert float(v[0]) == pytest.approx(10.5)


def test_gridfunction_rejects_evil():
    with pytest.raises(Exception):
        CartGridFunction("__import__('os')", dim=1)
    with pytest.raises(Exception):
        CartGridFunction("X_0.__class__", dim=1)


def test_function_from_db_vector():
    db = parse_input_string("""
    V {
       function_0 = "X_1"
       function_1 = "-X_0"
    }
    """)
    f = function_from_db(db.get_database("V"), dim=2)
    out = f((jnp.array([1.0]), jnp.array([2.0])))
    assert float(out[0][0]) == 2.0
    assert float(out[1][0]) == -1.0


def test_timer_report():
    tm = TimerManager()
    with tm.scope("IB::spreadForce"):
        pass
    with tm.scope("IB::spreadForce"):
        pass
    rep = tm.report()
    assert "IB::spreadForce" in rep
    assert tm.timers["IB::spreadForce"].count == 2


def test_metrics_jsonl(tmp_path):
    path = os.path.join(tmp_path, "m.jsonl")
    with MetricsLogger(path) as m:
        m.log({"step": 1, "dt": np.float64(0.5), "cfl": jnp.array(0.9)})
    rec = json.loads(open(path).read().strip())
    assert rec == {"step": 1, "dt": 0.5, "cfl": pytest.approx(0.9)}


class FakeState(NamedTuple):
    u: jnp.ndarray
    markers: jnp.ndarray
    t: jnp.ndarray


def _mkstate(seed):
    rng = np.random.default_rng(seed)
    return FakeState(
        u=jnp.asarray(rng.standard_normal((4, 4)), dtype=jnp.float32),
        markers=jnp.asarray(rng.standard_normal((7, 2)), dtype=jnp.float32),
        t=jnp.asarray(1.25, dtype=jnp.float32),
    )


def test_checkpoint_roundtrip(tmp_path):
    state = _mkstate(0)
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, state, step=42, metadata={"note": "hi"})
    assert latest_step(d) == 42
    template = _mkstate(99)  # different values, same structure
    restored, step, meta = restore_checkpoint(d, template)
    assert step == 42
    assert meta["note"] == "hi"
    np.testing.assert_array_equal(np.asarray(restored.u), np.asarray(state.u))
    np.testing.assert_array_equal(
        np.asarray(restored.markers), np.asarray(state.markers))
    assert float(restored.t) == pytest.approx(1.25)


def test_restart_lowers_to_the_fresh_program(tmp_path):
    """A state restored into a fresh (uncommitted) template must lower
    to the same program the fresh run compiled, or the restart misses
    the compile cache and pays the whole compile again (PR 23: 130 s at
    the flagship). A committed template keeps its placement."""
    import jax

    state = _mkstate(0)
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, state, step=1)
    restored, _, _ = restore_checkpoint(d, _mkstate(99))
    assert not any(leaf.committed
                   for leaf in jax.tree_util.tree_leaves(restored))
    step = jax.jit(lambda s: s._replace(u=s.u * 2.0))
    assert step.lower(restored).as_text() == step.lower(state).as_text()

    dev = jax.devices()[1]
    placed = jax.tree_util.tree_map(lambda a: jax.device_put(a, dev),
                                    _mkstate(99))
    restored, _, _ = restore_checkpoint(d, placed)
    assert all(leaf.committed and leaf.devices() == {dev}
               for leaf in jax.tree_util.tree_leaves(restored))


def test_checkpoint_prune(tmp_path):
    d = str(tmp_path / "ckpt")
    s = _mkstate(1)
    for i in range(5):
        save_checkpoint(d, s, step=i, keep=2)
    steps = sorted(int(f.split(".")[1]) for f in os.listdir(d)
                   if f.endswith(".npz"))
    assert steps == [3, 4]


def test_gridfunction_piecewise_conditionals():
    f = CartGridFunction("X_0 if X_0 > 0.5 else 0.0", dim=1)
    x = jnp.array([0.25, 0.75])
    out = np.asarray(f((x,)))
    np.testing.assert_allclose(out, [0.0, 0.75])
    g = CartGridFunction("(X_0 > 0.2 and X_0 < 0.8) * 2.0", dim=1)
    out = np.asarray(g((x,)))
    np.testing.assert_allclose(out, [2.0, 2.0])


def test_checkpoint_schema_mismatch_diagnosed(tmp_path):
    """A refactored state layout produces a named schema diff, not a
    silent orphan or a bare KeyError (VERDICT round 1, weak #9)."""
    import jax.numpy as jnp
    import pytest
    from ibamr_tpu.utils.checkpoint import (restore_checkpoint,
                                            save_checkpoint)

    state = {"u": jnp.zeros((4, 4)), "t": jnp.zeros(())}
    save_checkpoint(str(tmp_path), state, 1)
    # same structure restores fine
    out, step, meta = restore_checkpoint(str(tmp_path), state)
    assert step == 1 and "schema" in meta
    # renamed leaf -> clear diagnostic naming both sides
    bad = {"u_new": jnp.zeros((4, 4)), "t": jnp.zeros(())}
    with pytest.raises(ValueError, match="u_new"):
        restore_checkpoint(str(tmp_path), bad)
    # reshaped leaf -> shape mismatch named
    bad2 = {"u": jnp.zeros((8, 8)), "t": jnp.zeros(())}
    with pytest.raises(ValueError, match="shape mismatch"):
        restore_checkpoint(str(tmp_path), bad2)


def test_async_checkpoint_writer(tmp_path):
    """Async writes must produce checkpoints identical to sync ones,
    keep ordering under multiple enqueues, and surface worker errors on
    wait (S6)."""
    import jax.numpy as jnp

    from ibamr_tpu.utils.checkpoint import (AsyncCheckpointWriter,
                                            latest_step,
                                            restore_checkpoint,
                                            save_checkpoint)

    state = {"u": jnp.arange(12.0).reshape(3, 4), "t": jnp.asarray(1.5)}
    sync_dir = str(tmp_path / "sync")
    async_dir = str(tmp_path / "async")
    save_checkpoint(sync_dir, state, 7)

    w = AsyncCheckpointWriter(async_dir, keep=2)
    for k in (5, 6, 7):
        st_k = {"u": state["u"] + k, "t": state["t"]}
        w.save(st_k, k)
    w.wait()
    assert latest_step(async_dir) == 7
    template = {"u": jnp.zeros((3, 4)), "t": jnp.asarray(0.0)}
    got, step, _ = restore_checkpoint(async_dir, template)
    assert step == 7
    import numpy as np
    assert np.allclose(np.asarray(got["u"]),
                       np.asarray(state["u"]) + 7)
    # keep=2 pruned the oldest
    assert latest_step(async_dir) == 7
    import os
    files = [f for f in os.listdir(async_dir) if f.endswith(".npz")]
    assert len(files) == 2
    w.close()

    # error propagation: unwritable directory surfaces on wait
    bad = AsyncCheckpointWriter("/proc/definitely/not/writable")
    bad.save(state, 1)
    import pytest
    with pytest.raises(Exception):
        bad.wait()
