"""The speculative commit-scan kernel against its XLA scan, bit for bit.

``kernels/route_spec_scan.py`` runs one chunk of the chunked router's
speculative greedy recurrence as one Pallas call; ``kernels.ref.
spec_scan_xla`` is the ``lax.scan`` it replaces. On the same score panel
they must agree exactly on every choice, every chosen score (the value
each commit gate compared), the whole queue trajectory and the time
trajectory, in float32 and float64, at widths that fill whole 128-lane
rows (1024), fall short of one (64) and spill one lane into a padded
row (1025), under every static variant of the step.
"""
import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from x64 import enable_x64

C = 19  # not a multiple of the unroll: the kernel's remainder steps run

CASES = {
    "plain": {},
    "mask_inf_rows": {"mask": True},
    "ties": {"ties": True, "mask": True},
    "time_drain": {"time": True},
    "request_drain_outage": {"drain": True},
    "deadline_tloc": {"deadline": True, "tloc": True, "mask": True},
    "valid_tail": {"valid": True, "time": True, "drain": True},
    "everything": {"mask": True, "time": True, "drain": True,
                   "deadline": True, "tloc": True, "valid": True},
}


def _inputs(rng, n, dtype, case):
    f = lambda x: jnp.asarray(x, dtype)  # noqa: E731
    basez = rng.uniform(1e-3, 1.0, (C, n))
    ftok = rng.uniform(1e9, 1e10, C)
    if case.get("ties"):
        # few distinct scores and no backlog term on half the requests:
        # many exact ties, which the lowest index must win
        basez = rng.integers(1, 3, (C, n)).astype(float)
        ftok[::2] = 0.0
    if case.get("mask"):
        basez[rng.random((C, n)) < 0.3] = np.inf   # invisible servers
        basez[[1, 5, C - 2]] = np.inf              # rows with none visible
    kw = dict(has_mask=bool(case.get("mask")))
    if case.get("time"):
        kw["drain_rate"] = f(rng.uniform(0.0, 2e4, n))
        kw["arrival"] = f(np.cumsum(rng.exponential(1e-4, C)))
    if case.get("drain"):
        kw["drain"] = f(rng.uniform(0.0, 50.0, C))
        kw["outage"] = jnp.asarray(rng.random(n) < 0.1)
    if case.get("deadline"):
        dl = rng.uniform(0.2, 1.5, C)
        dl[::7] = np.inf
        kw["deadline"] = f(dl)
    if case.get("tloc"):
        tl = rng.uniform(0.0, 0.6, C)
        tl[::3] = 0.0
        kw["tloc"] = f(tl)
    if case.get("valid"):
        kw["valid"] = jnp.arange(C) < C - 5
    args = (f(basez), f(ftok), f(rng.integers(1, 64, C).astype(float)),
            f(rng.uniform(0.0, 1e3, n)), f(1e-5),
            f(rng.uniform(5e13, 2e14, n)))
    return args, kw


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("n", [64, 1024, 1025])
@pytest.mark.parametrize("x64", [False, True], ids=["f32", "f64"])
def test_spec_scan_kernel_matches_xla_scan(x64, n, case):
    with enable_x64() if x64 else contextlib.nullcontext():
        dtype = jnp.float64 if x64 else jnp.float32
        rng = np.random.default_rng([n, list(CASES).index(case)])
        args, kw = _inputs(rng, n, dtype, CASES[case])
        flags = {"has_mask": kw.pop("has_mask")}
        want, got = (
            jax.jit(functools.partial(ops.route_spec_scan, **flags,
                                      unroll=4, backend=backend))(*args, **kw)
            for backend in ("xla", "pallas-interpret"))
        names = ("queues", "choices", "lats", "times")
        for name, g, w in zip(names, got, want):
            if w is None:
                assert g is None, name
                continue
            assert g.dtype == w.dtype and g.shape == w.shape, name
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=name)
        if CASES[case].get("ties"):
            # the tie cases must really tie: some row's min repeats
            lats0 = np.asarray(args[0])[0]
            assert (lats0 == lats0.min()).sum() > 1
        if CASES[case].get("mask"):
            # an all-+inf row picks lane 0 and commits nothing
            assert int(got[1][1]) == 0
            assert np.isinf(np.asarray(got[2])[1])

