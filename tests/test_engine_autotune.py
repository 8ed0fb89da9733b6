"""Engine autotune: ``kops.use_fused`` picks fused vs unfused per
(family, p, n, k, dtype) — env pin > cache > measurement > heuristic —
and the projection-family dispatch honors it bit-exactly at trace time
(the BENCH_PR5 cimmino batch-1 regression, fixed by falling back)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import solvers
from repro.data import linsys
from repro.kernels import ops as kops
from repro.runtime import spans
from repro.solvers.store import FactorStore

PRM_APC = {"gamma": 1.0, "eta": 1.0}


@pytest.fixture(autouse=True)
def _clean_engine_cache(monkeypatch):
    # heuristic-only resolution by default: deterministic on any host
    monkeypatch.setenv(kops.AUTOTUNE_ENV, "0")
    monkeypatch.delenv(kops.ENGINE_ENV, raising=False)
    kops.engine_cache_clear()
    yield
    kops.engine_cache_clear()


@pytest.fixture(scope="module")
def sys_():
    return linsys.conditioned_gaussian(n=64, m=2, cond=10.0, seed=0)


# ---------------------------------------------------------------------------
# resolution order
# ---------------------------------------------------------------------------


def test_env_pin_wins_and_skips_the_cache(monkeypatch):
    monkeypatch.setenv(kops.ENGINE_ENV, "fused")
    assert kops.use_fused("cimmino", 32, 128, 1) is True
    monkeypatch.setenv(kops.ENGINE_ENV, "unfused")
    assert kops.use_fused("apc", 32, 128, 16) is False
    assert kops.engine_cache() == {}             # pins are never cached
    monkeypatch.setenv(kops.ENGINE_ENV, "both")
    with pytest.raises(ValueError, match="fused"):
        kops.use_fused("apc", 32, 128, 1)


def test_heuristic_cimmino_subbatch_falls_back():
    # the measured BENCH trend: fused loses ONLY at the single-RHS
    # cimmino corner (k=1 stays unpadded); any real batch pads onto the
    # 8-sublane tile and keeps the fused engine
    assert kops.use_fused("cimmino", 32, 128, 1) is False
    assert kops.use_fused("cimmino", 32, 128, 4) is True
    assert kops.use_fused("cimmino", 32, 128, 16) is True
    assert kops.use_fused("apc", 32, 128, 1) is True
    assert kops.use_fused("apc", 32, 128, 16) is True


def test_choice_is_cached_per_padded_shape():
    kops.use_fused("cimmino", 30, 100, 1, jnp.float32)
    key = ("cimmino", 32, 128, 1, "float32")     # (8, 128)-padded, k=1
    assert kops.engine_cache() == {key: False}
    # k pads to the 8-sublane tile: 9 and 16 share one cache entry
    kops.use_fused("apc", 32, 128, 9, jnp.float32)
    kops.use_fused("apc", 32, 128, 16, jnp.float32)
    assert ("apc", 32, 128, 16, "float32") in kops.engine_cache()
    assert len(kops.engine_cache()) == 2


def test_measured_autotune_runs_and_caches(monkeypatch):
    monkeypatch.setenv(kops.AUTOTUNE_ENV, "1")
    got = kops.use_fused("cimmino", 16, 128, 1, jnp.float32,
                         interpret=True)
    assert isinstance(got, bool)                 # whichever engine WON
    assert ("cimmino", 16, 128, 1, "float32") in kops.engine_cache()
    # second call is a cache hit (same answer, no re-measurement)
    assert kops.use_fused("cimmino", 16, 128, 1, jnp.float32,
                          interpret=True) is got


def test_measurements_run_under_autotune_spans(monkeypatch):
    """Every measurement opens a ``repro.ops.autotune`` span, the engine
    comparison around the tile searches its fused candidate runs (so the
    spans nest and their self time is the time measured); a cache hit
    opens none."""
    monkeypatch.setenv(kops.AUTOTUNE_ENV, "1")
    kops.bn_cache_clear()
    kops.tile_cache_clear()
    spans.reset()
    try:
        got = kops.use_fused("apc", 16, 256, 16, jnp.float32,
                             interpret=True)
        t = spans.totals()["repro.ops.autotune"]
        assert t.count >= 2 and 0 < t.self_s < t.total_s
        spans.reset()
        assert kops.use_fused("apc", 16, 256, 16, jnp.float32,
                              interpret=True) is got
        assert "repro.ops.autotune" not in spans.totals()
    finally:
        spans.reset()
        kops.bn_cache_clear()
        kops.tile_cache_clear()


@pytest.mark.parametrize("family,solves", [("apc", False),
                                           ("cimmino", True)])
def test_measured_unfused_candidate_is_the_dispatched_step(monkeypatch,
                                                           family, solves):
    """The ``unfused`` candidate the measurement times is the step the
    dispatch falls back to: for dense ``apc`` the pinv step, which solves
    nothing; for ``cimmino`` the Cholesky step, two triangular solves."""
    monkeypatch.setenv(kops.AUTOTUNE_ENV, "1")
    built = []
    candidates = kops._engine_candidates

    def recording(*args, **kwargs):
        built.append(candidates(*args, **kwargs))
        return built[-1]

    monkeypatch.setattr(kops, "_engine_candidates", recording)
    kops.use_fused(family, 16, 128, 1, jnp.float32, interpret=True)
    assert len(built) == 1
    jaxpr = str(jax.make_jaxpr(built[0]["unfused"])())
    assert ("triangular_solve" in jaxpr) is solves
    assert "dot_general" in jaxpr


def test_unknown_family_rejected():
    with pytest.raises(ValueError, match="family"):
        kops.use_fused("dgd", 32, 128, 1)


# ---------------------------------------------------------------------------
# dispatch regression: the serving path must not lose to unfused
# ---------------------------------------------------------------------------


def test_cimmino_batch1_dispatch_bit_equals_unfused(sys_):
    """The BENCH_PR5 regression corner (0.88x): with the autotune saying
    'unfused', use_kernel=True at k=1 must trace the IDENTICAL unfused
    step — bit-equal results, not just close."""
    s = solvers.get("cimmino")
    b = np.random.default_rng(0).standard_normal(sys_.N)
    kern = s.solve_many(sys_, b[None], iters=25, use_kernel=True,
                        store=FactorStore())
    ref = s.solve_many(sys_, b[None], iters=25, use_kernel=False,
                       store=FactorStore())
    assert np.array_equal(np.asarray(kern.x), np.asarray(ref.x))


def test_cimmino_batch1_pin_forces_the_fused_kernels(monkeypatch, sys_):
    s = solvers.get("cimmino")
    b = np.random.default_rng(0).standard_normal(sys_.N)
    monkeypatch.setenv(kops.ENGINE_ENV, "fused")
    kern = s.solve_many(sys_, b[None], iters=25, use_kernel=True,
                        store=FactorStore())
    ref = s.solve_many(sys_, b[None], iters=25, use_kernel=False,
                       store=FactorStore())
    # genuinely a different engine (different rounding), same solve
    assert not np.array_equal(np.asarray(kern.x), np.asarray(ref.x))
    assert np.allclose(np.asarray(kern.x), np.asarray(ref.x),
                       rtol=1e-10, atol=1e-12)


def test_apc_dispatch_keeps_fused_at_batch_16(sys_):
    """APC stays on the fused engine (heuristic) — and the fused batch-16
    path agrees with unfused to fp tolerance."""
    s = solvers.get("apc")
    B = np.random.default_rng(1).standard_normal((16, sys_.N))
    kern = s.solve_many(sys_, B, iters=25, use_kernel=True,
                        store=FactorStore(), **PRM_APC)
    ref = s.solve_many(sys_, B, iters=25, use_kernel=False,
                       store=FactorStore(), **PRM_APC)
    assert np.allclose(np.asarray(kern.x), np.asarray(ref.x),
                       rtol=1e-8, atol=1e-10)


# ---------------------------------------------------------------------------
# tile autotune: only candidates the TPU compiler accepts are measured
# ---------------------------------------------------------------------------

# the shapes tests/test_tpu_compile.py compiles for a described v5e chip
REAL_SHAPES = [(2048, 8192, 1, jnp.float32), (2048, 8192, 16, jnp.float32),
               (4096, 32768, 16, jnp.float32),
               (2048, 8192, 16, jnp.bfloat16)]


@pytest.mark.parametrize("p, n, k, dt", REAL_SHAPES)
def test_measured_tile_candidates_fit_vmem(monkeypatch, p, n, k, dt):
    """Every (BN, BP, BK) that ``pick_bn``/``pick_tiles`` would time obeys
    the TPU block rule and fits the VMEM budget (no measurement here: the
    timing function is replaced by a recorder)."""
    seen = []

    def record(p_pad, n_pad, k_pad, dtype, bn, bp_, bk, interpret):
        seen.append((k_pad, bn, bp_, bk))        # BN is timed at k=8
        return float(len(seen))                  # first candidate wins

    monkeypatch.setattr(kops, "_measure_pair", record)
    monkeypatch.setenv(kops.AUTOTUNE_ENV, "1")
    for env in (kops.BN_ENV, kops.BP_ENV, kops.BK_ENV):
        monkeypatch.delenv(env, raising=False)
    kops.bn_cache_clear()
    kops.tile_cache_clear()
    try:
        chosen = kops.pick_tiles(n, p, k, np.dtype(dt), interpret=False)
    finally:
        kops.bn_cache_clear()
        kops.tile_cache_clear()
    assert seen and (k,) + chosen in seen
    for k_pad, bn, bp_, bk in seen:
        assert n % bn == 0 and bn % 128 == 0
        assert p % bp_ == 0 and (bp_ == p or bp_ % 128 == 0)
        assert k_pad % bk == 0 and (bk == k_pad or bk % 8 == 0)
        assert kops.tile_fits(bn, bp_, bk, np.dtype(dt)), (bn, bp_, bk)


def test_default_tiles_cut_p_only_where_whole_p_overflows():
    f32 = np.dtype(np.float32)
    assert kops.default_tiles(512, 2048, 16, f32) == (2048, 16)
    assert kops.default_tiles(1024, 2048, 16, f32) == (1024, 16)
    assert kops.default_tiles(512, 4096, 16, f32) == (2048, 16)
    # the bf16 stream halves the tile bytes: whole p fits again
    assert kops.default_tiles(512, 4096, 16, np.dtype(jnp.bfloat16)) \
        == (4096, 16)
