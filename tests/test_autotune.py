"""Backend policy + block-shape autotuner coverage (ISSUE-7 acceptance).

Contracts under test:

* backend resolution: tpu -> tpu-mosaic, gpu/cuda/rocm -> gpu-triton with
  ``interpret=False`` (the regression for the old default-interpret
  trap that silently interpreted on GPU), everything else -> interpret;
  precedence of explicit record/name > set_backend/scope >
  ``REPRO_BACKEND`` env > platform;
* ``block_plan_fits`` reads its admission budget from the Backend record
  (GPU gets the shared-memory gate, not TPU's VMEM budget);
* GPU plans never interpret: ``geometry_ops`` under a gpu backend yields
  ``interpret=False`` plans whose megakernel REFUSES (``make_block_step``
  -> None) beyond the SMEM budget, and the fused Gaussian map refuses into
  the XLA map beyond the single-d-block bound;
* split-k kernel variants (the parallel-grid lowerings) match the oracles
  elementwise in interpret mode;
* tuner: ``deterministic`` bitwise-matches the static ``pick_block`` plan,
  cache round-trip (persist -> fresh reload -> ZERO re-timing), corrupt /
  stale-version cache files fall back cleanly, tuned candidates all
  produce elementwise-parity results, explicit ``block_*`` overrides are
  honored, and ``pick_block`` edge extents behave.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune
from repro.kernels.backend import (
    BACKEND_ENV,
    MEGAKERNEL_BUDGET_GPU,
    backend_scope,
    fused_map_admissible,
    resolve_backend,
    set_backend,
)
from repro.kernels.fused_loop import block_plan_fits, block_vmem_bytes
from repro.kernels.kermatvec import feature_contract_pallas
from repro.kernels.logmatvec import log_feature_contract_pallas
from repro.kernels.ops import (
    gaussian_feature_map,
    geometry_ops,
)
from repro.kernels.ref import (
    feature_contract_ref,
    gaussian_feature_map_ref,
    log_feature_contract_ref,
)
from repro.kernels.tiling import LANE, pick_block, round_up
from repro.core.geometry import FactoredPositive

KEY = jax.random.PRNGKey(7)


@pytest.fixture(autouse=True)
def _clean_policy(monkeypatch, tmp_path):
    """Every test starts from a pristine policy: no process override, no
    env override, deterministic tuner pointed at a throwaway cache."""
    monkeypatch.delenv(BACKEND_ENV, raising=False)
    monkeypatch.delenv(autotune.TUNE_ENV, raising=False)
    monkeypatch.setenv(autotune.CACHE_ENV, str(tmp_path / "tuning.json"))
    prev = set_backend(None)
    prev_cfg = autotune.configure(_reset=True)
    autotune.clear_cache()
    autotune.reset_stats()
    yield
    set_backend(prev)
    autotune._CONFIG.update(prev_cfg)
    autotune.clear_cache()
    autotune.reset_stats()


def _platform(monkeypatch, name):
    monkeypatch.setattr(jax, "default_backend", lambda: name)


# ---------------------------------------------------------------------------
# Backend resolution
# ---------------------------------------------------------------------------


def test_platform_defaults(monkeypatch):
    _platform(monkeypatch, "tpu")
    be = resolve_backend()
    assert (be.name, be.interpret, be.split_reduce) == \
        ("tpu-mosaic", False, False)
    _platform(monkeypatch, "cpu")
    assert resolve_backend().name == "interpret"
    assert resolve_backend().interpret is True


@pytest.mark.parametrize("platform", ["gpu", "cuda", "rocm"])
def test_gpu_never_interprets_silently(monkeypatch, platform):
    """THE regression: the old policy was ``interpret = backend != tpu``,
    which ran every kernel interpreted on GPU. A gpu platform must resolve
    to a compiled backend unless explicitly overridden."""
    _platform(monkeypatch, platform)
    be = resolve_backend()
    assert be.name == "gpu-triton"
    assert be.interpret is False
    assert be.split_reduce is True
    # the ambient resolution keeps the compiled gpu policy
    assert resolve_backend(None).name == "gpu-triton"
    # the interpreter stays reachable, but only by EXPLICIT name
    assert resolve_backend("interpret").interpret is True


def test_override_precedence(monkeypatch):
    _platform(monkeypatch, "cpu")
    # env beats platform
    monkeypatch.setenv(BACKEND_ENV, "gpu-triton")
    assert resolve_backend().name == "gpu-triton"
    # set_backend beats env
    set_backend("tpu-mosaic")
    assert resolve_backend().name == "tpu-mosaic"
    # explicit name beats set_backend
    assert resolve_backend("interpret").name == "interpret"
    # explicit record beats everything
    rec = resolve_backend("gpu-triton")
    assert resolve_backend(rec) is rec
    set_backend(None)
    assert resolve_backend().name == "gpu-triton"   # env again


def test_backend_scope_restores(monkeypatch):
    _platform(monkeypatch, "cpu")
    with backend_scope("gpu-triton") as be:
        assert be.name == "gpu-triton"
        assert resolve_backend().name == "gpu-triton"
    assert resolve_backend().name == "interpret"


def test_unknown_backend_name_raises():
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("cuda-graphs")


# ---------------------------------------------------------------------------
# Budgets / admission
# ---------------------------------------------------------------------------


def test_block_plan_fits_reads_backend_budget():
    gpu = resolve_backend("gpu-triton")
    tpu = resolve_backend("tpu-mosaic")
    interp = resolve_backend("interpret")
    # small problem: inside the TPU's VMEM budget; the lane-padded carries
    # alone exceed the 192 KiB SMEM gate
    assert block_plan_fits(64, 64, 32, backend=tpu)
    assert block_vmem_bytes(64, 64, 32) > MEGAKERNEL_BUDGET_GPU
    assert not block_plan_fits(64, 64, 32, backend=gpu)
    # the largest admitted n = m bucket at r = 256, and the next one up
    assert block_plan_fits(2048, 2048, 256, backend=tpu)
    assert not block_plan_fits(4096, 4096, 256, backend=tpu)
    assert block_plan_fits(4096, 4096, 256, backend=interp)
    # a record with megakernel lowering disabled refuses at ANY size
    off = tpu._replace(megakernel=False)
    assert not block_plan_fits(8, 8, 8, backend=off)


def test_gpu_plan_metadata_never_interpret():
    """A geometry plan built for gpu-triton: interpret=False end to end,
    megakernel refuses beyond SMEM instead of interpreting."""
    n, m, r = 1024, 1024, 256
    xi = jax.random.uniform(KEY, (n, r)) + 0.05
    zt = jax.random.uniform(jax.random.fold_in(KEY, 1), (m, r)) + 0.05
    geom = FactoredPositive(xi=xi, zeta=zt, eps=0.5)
    a = jnp.full((n,), 1.0 / n)
    b = jnp.full((m,), 1.0 / m)
    plan = geometry_ops(geom, backend=resolve_backend("gpu-triton"))
    assert plan.interpret is False
    assert plan.backend.name == "gpu-triton"
    assert plan.make_block_step(a, b, inner_steps=4) is None
    # the same shape on tpu-mosaic admits the megakernel
    plan_tpu = geometry_ops(geom, backend=resolve_backend("tpu-mosaic"))
    assert plan_tpu.make_block_step(a, b, inner_steps=4) is not None


def test_fused_map_admissibility_and_refusal():
    gpu = resolve_backend("gpu-triton")
    assert fused_map_admissible(2, gpu)
    assert fused_map_admissible(512, gpu)
    assert not fused_map_admissible(513, gpu)
    # no single-block constraint on sequential-grid backends
    assert fused_map_admissible(513, resolve_backend("tpu-mosaic"))
    assert fused_map_admissible(513, resolve_backend("interpret"))
    # the refusal EXECUTES (XLA map, no pallas lowering attempted) and
    # matches the oracle — on this CPU container a gpu-triton pallas_call
    # would fail to compile, so reaching the ref path IS the assertion.
    n, r, d = 24, 9, 513
    x = jax.random.normal(KEY, (n, d))
    anchors = jax.random.normal(jax.random.fold_in(KEY, 2), (r, d))
    c = jnp.full((r,), -0.5 * np.log(r))
    for log_space in (False, True):
        got = gaussian_feature_map(x, anchors, c, inv_eps=0.8,
                                   log_space=log_space, backend=gpu)
        want = gaussian_feature_map_ref(x, anchors, c, inv_eps=0.8,
                                        log_space=log_space)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Split-k lowerings (parallel-grid variants) vs oracles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,r,B", [(19, 3, 1), (200, 129, 5), (64, 127, 2)])
def test_splitk_contract_matches_oracle(n, r, B):
    xi = jax.random.uniform(KEY, (n, r)) + 0.1
    u = jax.random.uniform(jax.random.fold_in(KEY, 3), (n, B)) + 0.1
    want = feature_contract_ref(xi, u)
    seq = feature_contract_pallas(xi, u, interpret=True)
    spl = feature_contract_pallas(xi, u, interpret=True, split_reduce=True)
    np.testing.assert_allclose(seq, want, rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(spl, want, rtol=2e-5, atol=1e-5)


@pytest.mark.parametrize("n,r,B", [(19, 3, 1), (200, 129, 2)])
def test_splitk_log_contract_matches_oracle(n, r, B):
    lw = jax.random.normal(KEY, (n, r)) * 3.0
    s = jax.random.normal(jax.random.fold_in(KEY, 4), (n, B)) * 3.0
    want = log_feature_contract_ref(lw, s)
    seq = log_feature_contract_pallas(lw, s, interpret=True)
    spl = log_feature_contract_pallas(lw, s, interpret=True,
                                      split_reduce=True)
    np.testing.assert_allclose(seq, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(spl, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# pick_block edges + prior table
# ---------------------------------------------------------------------------


def test_pick_block_edges():
    assert pick_block(1) == LANE                      # size 1 -> one lane
    assert pick_block(512) == 512                     # size == cap
    assert pick_block(513) == 512                     # just past cap
    assert pick_block(200) == 256                     # non-lane-multiple
    assert pick_block(128) == 128
    assert pick_block(64, cap=256) == 128
    assert pick_block(1000, cap=256) == 256


def test_feature_map_prior_owns_the_256_cap():
    """The n-cap of 256 moved out of feature_map.py into the PRIOR table."""
    plan = autotune.static_plan(
        "feature_map", {"n": 4096, "r": 512, "d": 64})
    assert plan == {"block_n": 256, "block_r": 512, "block_d": 128}


def test_static_plan_forces_single_seq_block_on_splitk_backends():
    gpu = resolve_backend("gpu-triton")
    plan = autotune.static_plan(
        "feature_map", {"n": 4096, "r": 512, "d": 300}, gpu)
    assert plan["block_d"] == round_up(300, LANE)     # d rides whole
    for cand in autotune.candidates(
            "feature_map", {"n": 4096, "r": 512, "d": 300}, gpu):
        assert cand["block_d"] == round_up(300, LANE)


def test_deterministic_bitwise_matches_static(monkeypatch):
    extents = {"n": 200, "r": 129, "B": 1}
    be = resolve_backend("interpret")
    want = autotune.static_plan("feature_contract", extents, be)
    got = autotune.resolve("feature_contract", extents, jnp.float32, be,
                           deterministic=True)
    assert got == want
    # default mode is deterministic too (no REPRO_TUNE, no configure)
    assert autotune.resolve("feature_contract", extents, jnp.float32,
                            be) == want
    assert autotune.stats()["trials"] == 0


def test_resolve_blocks_honors_explicit_overrides():
    got = autotune.resolve_blocks(
        "feature_contract", {"n": 200, "r": 129, "B": 1},
        {"block_n": 128, "block_r": None}, jnp.float32, True, None)
    assert got["block_n"] == 128                      # explicit wins
    assert got["block_r"] == pick_block(129)          # hole filled


def test_candidates_start_from_static_plan():
    extents = {"n": 2048, "r": 256, "B": 1}
    be = resolve_backend("interpret")
    cands = autotune.candidates("feature_contract", extents, be)
    assert cands[0] == autotune.static_plan("feature_contract", extents, be)
    assert 1 < len(cands) <= 8
    assert len({tuple(sorted(c.items())) for c in cands}) == len(cands)


# ---------------------------------------------------------------------------
# Measured tuning + persistent cache
# ---------------------------------------------------------------------------

_EXTENTS = {"n": 200, "r": 129, "B": 1}


def _tune_once():
    be = resolve_backend("interpret")
    return autotune.resolve("feature_contract", _EXTENTS, jnp.float32, be,
                            deterministic=False)


def test_cache_roundtrip_zero_retiming(tmp_path):
    path = tmp_path / "cache" / "tuning.json"
    autotune.configure(cache_path=str(path), deterministic=False)
    plan = _tune_once()
    assert set(plan) == {"block_n", "block_r"}
    first = autotune.stats()
    assert first["trials"] > 0 and first["keys_tuned"] == 1
    assert path.exists()
    payload = json.loads(path.read_text())
    assert payload["version"] == autotune.CACHE_VERSION
    (entry,) = payload["entries"].values()
    assert entry["blocks"] == plan

    # same process: memory hit, zero new trials
    autotune.reset_stats()
    assert _tune_once() == plan
    assert autotune.stats()["trials"] == 0
    assert autotune.stats()["memory_hits"] == 1

    # simulated fresh process: drop in-memory state, reload from disk
    autotune.clear_cache()
    autotune.reset_stats()
    assert _tune_once() == plan
    stats = autotune.stats()
    assert stats["trials"] == 0 and stats["keys_tuned"] == 0
    assert stats["disk_hits"] == 1


@pytest.mark.parametrize("payload", [
    "{ not json",
    json.dumps({"version": 999, "entries": {"k": {"blocks": {"block_n": 1}}}}),
    json.dumps({"entries": "nope"}),
    json.dumps([1, 2, 3]),
])
def test_corrupt_or_stale_cache_falls_back(tmp_path, payload):
    path = tmp_path / "tuning.json"
    path.write_text(payload)
    autotune.configure(cache_path=str(path), deterministic=False)
    plan = _tune_once()
    assert autotune.stats()["keys_tuned"] == 1        # re-timed, no crash
    # and the file was rewritten as a valid current-version cache
    fresh = json.loads(path.read_text())
    assert fresh["version"] == autotune.CACHE_VERSION
    (entry,) = fresh["entries"].values()
    assert entry["blocks"] == plan


def test_tuned_candidates_all_match_oracle():
    """Whatever plan the tuner lands on, numerics are unchanged: every
    candidate block shape produces the oracle result elementwise."""
    be = resolve_backend("interpret")
    for n, r, B in [(19, 3, 1), (200, 129, 5), (64, 127, 2)]:
        xi = jax.random.uniform(KEY, (n, r)) + 0.1
        u = jax.random.uniform(jax.random.fold_in(KEY, 5), (n, B)) + 0.1
        want = feature_contract_ref(xi, u)
        for cand in autotune.candidates(
                "feature_contract", {"n": n, "r": r, "B": B}, be):
            got = feature_contract_pallas(xi, u, interpret=True, **cand)
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)


def test_tuning_scope_and_env(monkeypatch, tmp_path):
    assert not autotune.tuning_enabled()
    monkeypatch.setenv(autotune.TUNE_ENV, "1")
    assert autotune.tuning_enabled()
    monkeypatch.delenv(autotune.TUNE_ENV)
    with autotune.tuning(cache_path=str(tmp_path / "t.json")):
        assert autotune.tuning_enabled()
        plan = _tune_once()
        assert autotune.stats()["keys_tuned"] == 1
        assert set(plan) == {"block_n", "block_r"}
    assert not autotune.tuning_enabled()


def test_unwritable_cache_dir_keeps_in_process_winner(monkeypatch):
    autotune.configure(cache_path="/proc/definitely/not/writable.json",
                       deterministic=False)
    plan = _tune_once()
    assert set(plan) == {"block_n", "block_r"}
    autotune.reset_stats()
    assert _tune_once() == plan                       # memory still serves
    assert autotune.stats()["memory_hits"] == 1
