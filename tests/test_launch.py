"""Launcher-layer unit tests: specs, shardings, loop-aware HLO analysis."""
import jax
import numpy as np

from repro.configs import get_config, get_shape
from repro.launch.hlo_analysis import parse_collectives, roofline_terms
from repro.launch.hlo_loops import analyze_hlo
from repro.launch.mesh import make_local_mesh
from repro.launch.specs import input_specs, param_shardings


def test_input_specs_cover_all_shapes():
    for arch in ("smollm_135m", "whisper_base", "internvl2_26b",
                 "mamba2_1p3b"):
        cfg = get_config(arch)
        for shp in ("train_4k", "prefill_32k", "decode_32k"):
            spec = input_specs(cfg, get_shape(shp))
            assert spec, (arch, shp)
            for leaf in jax.tree.leaves(spec):
                assert isinstance(leaf, jax.ShapeDtypeStruct)


def test_param_shardings_divisible():
    """Every assigned mesh axis must divide its dim, for every leaf."""
    mesh = make_local_mesh(1, 1)
    for arch in ("smollm_135m", "deepseek_v3_671b", "zamba2_1p2b"):
        cfg = get_config(arch).tiny()
        p_shape = jax.eval_shape(
            lambda k: __import__("repro.models", fromlist=["init_params"]
                                 ).init_params(k, cfg),
            jax.random.PRNGKey(0))
        shards = param_shardings(mesh, cfg, p_shape)
        for leaf, sh in zip(jax.tree.leaves(p_shape),
                            jax.tree.leaves(shards)):
            for dim, axes in zip(leaf.shape, sh.spec):
                if axes is None:
                    continue
                axes = axes if isinstance(axes, tuple) else (axes,)
                total = 1
                for a in axes:
                    total *= mesh.shape[a]
                assert dim % total == 0, (leaf.shape, sh.spec)


def test_parse_collectives_ring_factors():
    hlo = """
  %ag = bf16[16,128] all-gather(%x), replica_groups=[16,16]
  %ar = f32[64] all-reduce(%y), replica_groups=[1,256]
  %cp = f32[8,8] collective-permute(%z)
"""
    st = parse_collectives(hlo, 256)
    assert st.counts == {"all-gather": 1, "all-reduce": 1,
                         "collective-permute": 1}
    ag = 16 * 128 * 2 * (15 / 16)
    ar = 2 * 64 * 4 * (255 / 256)
    cp = 8 * 8 * 4
    np.testing.assert_allclose(st.wire_bytes, ag + ar + cp, rtol=1e-6)


def test_roofline_terms_dominance():
    t = roofline_terms(197e12, 819e9 * 2, 50e9 * 0.5)   # 1s/2s/0.5s
    assert t["dominant"] == "memory"
    np.testing.assert_allclose(t["compute_s"], 1.0)
    np.testing.assert_allclose(t["memory_s"], 2.0)
    np.testing.assert_allclose(t["collective_s"], 0.5)


def test_loop_aware_analyzer_multiplies_trip_counts():
    """A dot inside a while body must count trip_count times."""
    hlo = """
%body (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %gte0 = s32[] get-tuple-element(%p), index=0
  %gte1 = f32[4,4] get-tuple-element(%p), index=1
  %d = f32[4,4] dot(%gte1, %gte1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %next = s32[] add(%gte0, %one)
  ROOT %t = (s32[], f32[4,4]) tuple(%next, %d)
}

%cond (p: (s32[], f32[4,4])) -> pred[] {
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[4,4]) -> f32[4,4] {
  %z = s32[] constant(0)
  %t0 = (s32[], f32[4,4]) tuple(%z, %a)
  %w = (s32[], f32[4,4]) while(%t0), condition=%cond, body=%body
  ROOT %out = f32[4,4] get-tuple-element(%w), index=1
}
"""
    res = analyze_hlo(hlo, 1)
    # one 4x4x4 dot (128 flops) x 7 trips
    np.testing.assert_allclose(res["flops_per_device"], 7 * 2 * 4 * 4 * 4)


def test_loop_aware_collectives_in_loops():
    hlo = """
%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8] get-tuple-element(%p), index=1
  %ar = f32[8] all-reduce(%x), replica_groups=[1,4], to_apply=%sum
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8]) tuple(%ni, %ar)
}

%sum (a: f32[], b: f32[]) -> f32[] {
  ROOT %s = f32[] add(%a, %b)
}

%cond (p: (s32[], f32[8])) -> pred[] {
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(3)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8]) tuple(%z, %a)
  %w = (s32[], f32[8]) while(%t0), condition=%cond, body=%body
  ROOT %out = f32[8] get-tuple-element(%w), index=1
}
"""
    res = analyze_hlo(hlo, 4)
    per = 2 * 8 * 4 * (3 / 4)
    np.testing.assert_allclose(res["wire_bytes_per_device"], 3 * per)
    assert res["collective_counts"]["all-reduce"] == 3


def test_compile_cache_dir_from_env_or_checkout(monkeypatch, tmp_path):
    """The cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, and no
    directory is set in code then; otherwise it is one fixed path at the
    root of the checkout."""
    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv(compile_cache.CACHE_ENV)
        path = compile_cache.enable_compile_cache()
        assert path == jax.config.jax_compilation_cache_dir
        assert path == str(compile_cache.DEFAULT_CACHE_DIR)
        assert (compile_cache.DEFAULT_CACHE_DIR.parent
                / "chip_smoke.py").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
