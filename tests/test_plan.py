"""Plan/execute split (repro.core.plan): numerical equivalence of planned
execution vs the direct-convolution oracle, plan-cache hit/miss behavior,
the transform-once contract, and plan-time measured autotuning."""

import jax
import jax.numpy as jnp
import pytest

from repro.core import winograd as wg
from repro.core.im2col import direct_conv2d
from repro.core.plan import (ConvPlan, clear_plan_cache, plan_cache_info,
                             plan_conv1d, plan_conv2d,
                             plan_depthwise_conv1d)

from conftest import rel_err

# (plan-cache isolation is provided by the autouse _fresh_plan_cache fixture
# in conftest.py)


def _spec_cache():
    """(hits, misses, size) of the spec cache -- plan_cache_info also
    carries the serialized-plan artifact counters (tested in
    test_compile.py), which these tests don't exercise."""
    info = plan_cache_info()
    return (info["hits"], info["misses"], info["size"])


# ---------------------------------------------------------------------------
# numerical equivalence: plan.apply == lax.conv_general_dilated
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kh,kw", [(3, 3), (5, 5), (1, 7), (7, 1)])
@pytest.mark.parametrize("padding", ["SAME", "VALID"])
@pytest.mark.parametrize("algorithm", ["winograd", "im2col", "pallas_winograd"])
def test_plan_apply_matches_direct(rng, kh, kw, padding, algorithm):
    x = jnp.asarray(rng.standard_normal((2, 12, 12, 4)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((kh, kw, 4, 5)) / (kh * kw),
                    jnp.float32)
    p = plan_conv2d(x.shape, w, padding=padding, algorithm=algorithm)
    got = p.apply(x)
    want = direct_conv2d(x, w, padding=padding)
    assert got.shape == want.shape
    assert p.out_shape == want.shape
    assert rel_err(got, want) < 1e-3


def test_plan_apply_under_jit(rng):
    x = jnp.asarray(rng.standard_normal((1, 14, 14, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 8, 8)) / 3, jnp.float32)
    p = plan_conv2d(x.shape, w, algorithm="winograd")
    got = jax.jit(p.apply)(x)
    assert rel_err(got, direct_conv2d(x, w)) < 1e-3


def test_plan_allows_different_batch_rejects_different_spatial(rng):
    w = jnp.asarray(rng.standard_normal((3, 3, 4, 4)) / 3, jnp.float32)
    p = plan_conv2d((1, 10, 10, 4), w)
    x3 = jnp.asarray(rng.standard_normal((3, 10, 10, 4)), jnp.float32)
    assert rel_err(p.apply(x3), direct_conv2d(x3, w)) < 1e-3
    with pytest.raises(ValueError, match="plan built for"):
        p.apply(jnp.zeros((1, 11, 10, 4), jnp.float32))


# ---------------------------------------------------------------------------
# plan cache hit/miss behavior
# ---------------------------------------------------------------------------

def test_cache_hit_on_same_shape_miss_on_new(rng):
    w = jnp.asarray(rng.standard_normal((3, 3, 4, 4)) / 3, jnp.float32)
    assert _spec_cache() == (0, 0, 0)
    p1 = plan_conv2d((1, 12, 12, 4), w)
    assert _spec_cache() == (0, 1, 1)
    p2 = plan_conv2d((1, 12, 12, 4), w)
    assert _spec_cache() == (1, 1, 1)
    assert p1.spec is p2.spec                  # decisions shared, not rebuilt
    plan_conv2d((1, 16, 16, 4), w)             # new spatial shape -> miss
    assert _spec_cache() == (1, 2, 2)
    plan_conv2d((1, 12, 12, 4), w, algorithm="im2col")   # new algorithm -> miss
    assert _spec_cache() == (1, 3, 3)


def test_cache_key_includes_padding_and_stride(rng):
    w = jnp.asarray(rng.standard_normal((3, 3, 4, 4)) / 3, jnp.float32)
    plan_conv2d((1, 12, 12, 4), w, padding="SAME")
    plan_conv2d((1, 12, 12, 4), w, padding="VALID")
    plan_conv2d((1, 12, 12, 4), w, stride=2)
    assert plan_cache_info()["misses"] == 3
    assert plan_cache_info()["hits"] == 0


def test_clear_plan_cache(rng):
    w = jnp.asarray(rng.standard_normal((3, 3, 4, 4)) / 3, jnp.float32)
    plan_conv2d((1, 12, 12, 4), w)
    clear_plan_cache()
    assert _spec_cache() == (0, 0, 0)


# ---------------------------------------------------------------------------
# the transform-once contract (the paper's section-4 deployment insight)
# ---------------------------------------------------------------------------

def test_filter_transform_called_exactly_once(rng, monkeypatch):
    """transform_filter_2d runs at plan time, once; repeated apply() calls
    reuse the cached Winograd-domain filter."""
    calls = {"n": 0}
    real = wg.transform_filter_2d

    def counting(*args, **kwargs):
        calls["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(wg, "transform_filter_2d", counting)
    x = jnp.asarray(rng.standard_normal((1, 12, 12, 4)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 4, 4)) / 3, jnp.float32)
    p = plan_conv2d(x.shape, w, algorithm="winograd")
    assert calls["n"] == 1
    for _ in range(3):
        p.apply(x)
    assert calls["n"] == 1


def test_no_geometry_derivation_in_apply(rng, monkeypatch):
    """apply() must not re-derive padding/tiling: _pad_amounts is plan-time
    only."""
    x = jnp.asarray(rng.standard_normal((1, 12, 12, 4)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 4, 4)) / 3, jnp.float32)
    p = plan_conv2d(x.shape, w, algorithm="winograd")

    def boom(*args, **kwargs):
        raise AssertionError("_pad_amounts called during apply()")

    monkeypatch.setattr(wg, "_pad_amounts", boom)
    p.apply(x)


def test_plan_records_build_time_and_domain_filter(rng):
    w = jnp.asarray(rng.standard_normal((3, 3, 4, 6)) / 3, jnp.float32)
    p = plan_conv2d((1, 12, 12, 4), w, algorithm="winograd")
    ct = p.spec.ct_h
    assert p.u.shape == (ct.t, ct.t, 4, 6)     # Winograd-domain filter
    assert p.build_time_s > 0


# ---------------------------------------------------------------------------
# plan-time measured autotuning
# ---------------------------------------------------------------------------

def test_auto_tuned_measures_once_and_caches_winner(rng):
    x_shape = (1, 20, 20, 8)
    w = jnp.asarray(rng.standard_normal((3, 3, 8, 8)) / 3, jnp.float32)
    p = plan_conv2d(x_shape, w, algorithm="auto_tuned")
    assert p.algorithm in ("winograd", "winograd_f63", "fft", "im2col")
    report = p.spec.autotune_report
    assert report is not None
    assert report["winner"] == p.algorithm
    assert report["t_winograd_s"] > 0 and report["t_im2col_s"] > 0
    # second plan of the same shape: cache hit, no re-measurement
    before = plan_cache_info()["hits"]
    p2 = plan_conv2d(x_shape, w, algorithm="auto_tuned")
    assert plan_cache_info()["hits"] == before + 1
    assert p2.spec is p.spec


def test_auto_tuned_counts_and_logs_a_failed_contender(rng, monkeypatch,
                                                       caplog):
    """A contender that raises is dropped from the race, but counted
    (plan.autotune.contender_failed) and logged with its exception."""
    from repro.core import plan as plan_mod
    from repro.obs import metrics

    real = plan_mod._build_spec

    def build(x_shape, w_shape, dtype, stride, padding, requested, resolved,
              *args, **kwargs):
        if resolved == "fft":
            raise RuntimeError("fft contender refused")
        return real(x_shape, w_shape, dtype, stride, padding, requested,
                    resolved, *args, **kwargs)

    monkeypatch.setattr(plan_mod, "_build_spec", build)
    counter = metrics.registry().counter("plan.autotune.contender_failed")
    before = counter.value
    w = jnp.asarray(rng.standard_normal((3, 3, 8, 8)) / 3, jnp.float32)
    with caplog.at_level("WARNING", logger=plan_mod.__name__):
        p = plan_conv2d((1, 20, 20, 8), w, algorithm="auto_tuned")
    assert counter.value == before + 1
    assert "t_fft_s" not in p.spec.autotune_report
    assert p.algorithm != "fft"
    assert any("fft" in r.getMessage() and r.exc_info
               for r in caplog.records)


def test_auto_tuned_falls_back_to_heuristic_under_jit(rng):
    """Planning inside a jit trace cannot measure; the static amortization
    predicate decides instead, and tracing must not crash."""
    w = jnp.asarray(rng.standard_normal((3, 3, 8, 8)) / 3, jnp.float32)

    @jax.jit
    def fwd(x, w):
        return plan_conv2d(x.shape, w, algorithm="auto_tuned").apply(x)

    x = jnp.asarray(rng.standard_normal((1, 20, 20, 8)), jnp.float32)
    assert rel_err(fwd(x, w), direct_conv2d(x, w)) < 1e-3


def test_auto_tuned_heuristic_fallback_is_not_cached(rng):
    """A heuristic decision made under a jit trace must not poison the cache:
    a later eager plan of the same shape still gets to measure."""
    w = jnp.asarray(rng.standard_normal((3, 3, 8, 8)) / 3, jnp.float32)
    x = jnp.asarray(rng.standard_normal((1, 20, 20, 8)), jnp.float32)
    jax.jit(lambda x, w: plan_conv2d(x.shape, w,
                                     algorithm="auto_tuned").apply(x))(x, w)
    p = plan_conv2d(x.shape, w, algorithm="auto_tuned")   # eager: measures
    assert p.spec.autotune_report is not None


def test_auto_tuned_unsuitable_layer_skips_measurement(rng):
    # stride 3: no winograd-family capability (stride 2 has the strided
    # phase-decomposition executor now), so auto_tuned must not measure.
    w = jnp.asarray(rng.standard_normal((3, 3, 4, 4)) / 3, jnp.float32)
    p = plan_conv2d((1, 12, 12, 4), w, stride=3, algorithm="auto_tuned")
    assert p.algorithm == "im2col"
    assert p.spec.autotune is None


def test_forced_winograd_on_uncovered_layer_raises(rng):
    """A forced algorithm with no matching capability raises the registry
    error, which must enumerate the executors that DO cover the layer."""
    w = jnp.asarray(rng.standard_normal((3, 3, 4, 4)) / 3, jnp.float32)
    with pytest.raises(ValueError, match="no executor"):
        plan_conv2d((1, 12, 12, 4), w, stride=3, algorithm="winograd")
    with pytest.raises(ValueError, match="im2col"):
        plan_conv2d((1, 12, 12, 4), w, stride=3, algorithm="winograd")


def test_stride2_plans_to_winograd_family(rng):
    """Stride-2 3x3 layers plan onto the phase-decomposition executors."""
    w = jnp.asarray(rng.standard_normal((3, 3, 4, 4)) / 3, jnp.float32)
    p = plan_conv2d((1, 12, 12, 4), w, stride=2, algorithm="winograd")
    assert p.algorithm == "winograd_strided"
    from repro.core.im2col import direct_conv2d
    x = jnp.asarray(rng.standard_normal((1, 12, 12, 4)), jnp.float32)
    assert rel_err(p.apply(x), direct_conv2d(x, w, stride=2)) < 1e-3


# ---------------------------------------------------------------------------
# conv1d plans (incl. polyphase stride-2)
# ---------------------------------------------------------------------------

def _direct_conv1d(x, w, stride):
    return jax.lax.conv_general_dilated(
        x[:, :, None], w[:, None], window_strides=(stride, 1),
        padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))[:, :, 0]


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("length", [20, 33])
def test_conv1d_plan_matches_direct(rng, stride, length):
    x = jnp.asarray(rng.standard_normal((2, length, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 8, 6)) / 3, jnp.float32)
    p = plan_conv1d(x.shape, w, stride=stride)
    got = p.apply(x)
    want = _direct_conv1d(x, w, stride)
    assert got.shape == want.shape
    assert rel_err(got, want) < 1e-4
    assert p.mode == ("as2d" if stride == 1 else "polyphase")


def test_conv1d_polyphase_subplans_are_pretransformed(rng):
    """The polyphase decomposition plans each stride-1 sub-filter once."""
    x = jnp.asarray(rng.standard_normal((1, 32, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 8, 6)) / 3, jnp.float32)
    p = plan_conv1d(x.shape, w, stride=2)
    assert len(p.subplans) == 2
    assert all(isinstance(s, ConvPlan) for s in p.subplans)


# ---------------------------------------------------------------------------
# depthwise causal Cook-Toom conv1d plans (Mamba's short conv)
# ---------------------------------------------------------------------------

def _direct_depthwise_causal(x, w):
    r = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (r - 1, 0), (0, 0)))
    return sum(xp[:, k:k + x.shape[1]] * w[k][None, None] for k in range(r))


@pytest.mark.parametrize("length,r", [(64, 4), (33, 4), (20, 3)])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_depthwise_plan_matches_direct(rng, length, r, backend):
    x = jnp.asarray(rng.standard_normal((2, length, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((r, 16)) / r, jnp.float32)
    p = plan_depthwise_conv1d(x.shape, w, backend=backend)
    got = p.apply(x)
    assert got.shape == x.shape
    assert rel_err(got, _direct_depthwise_causal(x, w)) < 1e-4


def test_depthwise_plan_decisions_are_cached(rng):
    """Second plan of the same (L, C) shape is a spec-cache hit: cook_toom,
    tile count, padding, and blocking are decided once per shape."""
    x_shape = (2, 48, 16)
    w = jnp.asarray(rng.standard_normal((4, 16)) / 4, jnp.float32)
    p1 = plan_depthwise_conv1d(x_shape, w)
    before = plan_cache_info()["hits"]
    p2 = plan_depthwise_conv1d(x_shape, w)
    assert plan_cache_info()["hits"] == before + 1
    assert p2.spec is p1.spec
    # batch may differ, L/C must match
    x5 = jnp.asarray(jnp.zeros((5,) + x_shape[1:]), jnp.float32)
    assert p1.apply(x5).shape == x5.shape
    with pytest.raises(ValueError, match="plan built for"):
        p1.apply(jnp.zeros((2, 47, 16), jnp.float32))


def test_depthwise_plan_taps_are_pretransformed(rng):
    """apply() never re-derives the transform set: u is already (t, C)."""
    w = jnp.asarray(rng.standard_normal((4, 8)) / 4, jnp.float32)
    p = plan_depthwise_conv1d((1, 32, 8), w)
    assert p.u.shape == (p.spec.ct.t, 8)
    assert p.spec.n_tiles == 8 and p.spec.ct.m == 4
