"""The plain reference, checked at a small size on the CPU before the chip
comparison uses it: its transforms against numpy's, its step against the
program's XLA scatter path, and its lower-precision controls against
itself.  Run by hand: ``JAX_PLATFORMS=cpu python -m pytest perfbench/tests``.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness, inputfile  # noqa: E402
from perfbench.reference import ib_shell  # noqa: E402

N, LAT = 16, 8


@pytest.fixture(scope="module")
def small():
    text = open(os.path.join(ROOT, "perfbench", "configs",
                             "ex4_shell_128.input3d")).read()
    text = inputfile.set_keys(text, {
        "CartesianGeometry": {"n_cells": [N, N, N]},
        "Shell": {"n_lat": LAT, "n_lon": LAT},
        "IBMethod": {"transfer_engine": "scatter"}})
    return text, inputfile.parse(text)


def test_input_rewrite_roundtrip(small):
    _, db = small
    assert db["CartesianGeometry"]["n_cells"] == [N, N, N]
    assert db["IBMethod"] == {"delta_fcn": "IB_4",
                              "transfer_engine": "scatter"}
    assert db["INSStaggeredHierarchyIntegrator"]["dt"] == 5e-5


def test_transforms_against_numpy(small):
    """The Helmholtz and Poisson solves invert the 7-point stencils that
    numpy applies by rolls, and scipy's transforms agree with numpy's."""
    ref = ib_shell.ShellReference(small[1])
    rng = np.random.default_rng(0)
    x = rng.standard_normal(ref.n)
    assert np.allclose(ref._fft(x), np.fft.rfftn(x), atol=1e-10)
    assert np.allclose(ref._ifft(np.fft.rfftn(x)), x, atol=1e-12)

    def lap(f):
        return sum((np.roll(f, -1, d) - 2 * f + np.roll(f, 1, d))
                   / ref.dx[d] ** 2 for d in range(3))

    a, b = 2.0e4, -0.025
    sol = ref._ifft(ref._fft(x) / (a + b * ref.lam))
    assert np.allclose(a * sol + b * lap(sol), x, atol=1e-9)
    x0 = x - x.mean()
    lam = np.where(ref.lam == 0, 1.0, ref.lam)
    phi = ref._ifft(np.where(ref.lam == 0, 0.0, ref._fft(x0) / lam))
    assert np.allclose(lap(phi), x0, atol=1e-8)
    ref.close()


def test_spread_interp_adjoint_and_moments(small):
    ref = ib_shell.ShellReference(small[1])
    rng = np.random.default_rng(1)
    X = ref.X0 + 0.01 * rng.standard_normal(ref.X0.shape)
    st = ref.stencils(X)
    for lin, w in st:
        assert np.allclose(w.sum(axis=1), 1.0, atol=1e-12)
    F = rng.standard_normal(X.shape)
    u = tuple(rng.standard_normal(ref.n) for _ in range(3))
    f = ref.spread(F, st)
    lhs = sum(np.sum(f[d] * u[d]) for d in range(3)) * np.prod(ref.dx)
    rhs = np.sum(F * ref.interp(u, st))
    assert abs(lhs - rhs) < 1e-10 * abs(rhs)
    ref.close()


def test_step_against_program_scatter_path(small, tmp_path):
    """Five steps from a mid-run state: the program's scatter path in
    float32 agrees with the reference to float32 rounding, and the controls
    do not."""
    import jax
    import jax.numpy as jnp

    from ibamr_tpu.models.shell3d import build_shell_example
    from ibamr_tpu.utils import parse_input_file

    text, db = small
    inp = tmp_path / "input3d"
    inp.write_text(text)
    integ, state = build_shell_example(input_db=parse_input_file(str(inp)),
                                       dtype=jnp.float32)
    rng = np.random.default_rng(0)
    u0 = tuple(jnp.asarray(5e-3 * rng.standard_normal((N, N, N)),
                           jnp.float32) for _ in range(3))
    s = state._replace(ins=state.ins._replace(u=u0))
    step = jax.jit(integ.step)
    for _ in range(3):
        s = step(s, 5e-5)
    adapter = harness.load_adapter(os.path.join(
        ROOT, "perfbench", "adapters", "ib_shell.py"))
    s_in = harness.to_host(adapter, s)
    for _ in range(5):
        s = step(s, 5e-5)
    ref = ib_shell.ShellReference(db)
    r_in = ib_shell.state_from_arrays(s_in)
    r_out = ref.advance(r_in, 5)
    got = adapter.compare(r_out, harness.to_host(adapter, s), r_in)
    assert got["du"] < 2e-4 and got["p"] < 1e-4 and got["U"] < 1e-4, got
    low = ib_shell.ShellReference(db, lowp="bf16")
    l_out = low.advance(r_in, 5)
    low.close()
    bad = adapter.compare(r_out, adapter.arrays_from(l_out), r_in)
    for key, least in (("du", 1e-2), ("p", 1e-3), ("U", 5e-4)):
        assert bad[key] > least and bad[key] > 10 * got[key], (key, bad)
    ref.close()
