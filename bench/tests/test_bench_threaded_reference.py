"""The threaded reference reads every answer as the plain one does: the
same numbers, chunk by chunk over a pool of threads, for a sound answer,
a wrong one and a broken one; the four-chip configuration names it."""
import jax
import numpy as np
import pytest

from bench import harness
from bench.references import gaussian_log_sinkhorn as plain
from bench.references import gaussian_log_sinkhorn_threaded as threaded


@pytest.fixture(scope="module")
def answer():
    kx, ky, ku = jax.random.split(jax.random.PRNGKey(3), 3)
    n, m, d, r, eps = 2048, 1024, 5, 32, 0.5
    x = jax.random.normal(kx, (n, d))
    y = jax.random.normal(ky, (m, d)) - 0.3
    R = float(max(np.linalg.norm(x, axis=1).max(),
                  np.linalg.norm(y, axis=1).max()))
    u = np.sqrt(plain.gaussian_q(R, eps, d) * eps / 4) * \
        jax.random.normal(ku, (r, d))
    res = plain.solve(x, y, u, eps=eps, R=R, tol=1e-6, max_iter=500)
    return x, y, u, res, dict(eps=eps, R=R)


@pytest.mark.parametrize("which", ["sound", "wrong", "broken"])
def test_threaded_check_reads_as_the_plain_one(answer, which, monkeypatch):
    monkeypatch.setattr(threaded, "CHUNK", 512)   # several chunks a side
    monkeypatch.setattr(threaded, "BLOCK", 128)
    x, y, u, res, kw = answer
    f, g, cost = np.asarray(res.f), np.asarray(res.g), float(res.cost)
    if which == "wrong":
        f = f + 0.01 * np.sin(np.arange(f.size))
    elif which == "broken":
        g = g.copy()
        g[7] = np.nan
    want = plain.check(x, y, u, f, g, cost, **kw)
    got = threaded.check(x, y, u, f, g, cost, **kw)
    assert set(got) == set(want)
    for key in want:
        if np.isfinite(want[key]):
            assert got[key] == pytest.approx(want[key], rel=1e-9,
                                             abs=1e-12), key
        else:
            assert got[key] == want[key], key
    if which == "sound":
        assert got["col_err"] < 1e-5


def test_four_chip_configuration_uses_it():
    cell = harness.load_cell("higgs_r256_x4.sharded_solve")
    assert cell.config["reference"] == "gaussian_log_sinkhorn_threaded"
    assert harness.load_cell("higgs_r256.solve").config["reference"] == \
        "gaussian_log_sinkhorn"
    ref = harness.load_module(harness.BENCH, "references",
                              cell.config["reference"])
    assert ref.solve is plain.solve and ref.BF16_3X == plain.BF16_3X
