"""Graph-level compile() API (repro.core.compile): lowering, fusion
pattern rewrites, placement, NetworkPlan execution parity, the serialized
deployment artifact (save/load round-trip, mismatch refusals, the
zero-filter-transform warm path), describe() table generation, and the
deprecation shims over the legacy plan_* entry points."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compile as compiler
from repro.core import plan as planlib
from repro.core import registry
from repro.core.compile import (ArtifactMismatchError, LayerIR, NetworkPlan,
                                fuse, infer_shapes, lower, place)
from repro.core.compile import compile as compile_network
from repro.core.im2col import direct_conv2d
from repro.core.plan import (InvertedResidualPlan, SeparableBlockPlan,
                             plan_cache_info)
from repro.models import audio, cnn

from conftest import rel_err

_RES = {"vgg16": 64, "vgg19": 64, "googlenet": 64, "inception_v3": 96,
        "squeezenet": 64, "mobilenet_v1": 64, "mobilenet_v1_050": 64,
        "mobilenet_v2": 64}


def _net(name, res=None, key=0):
    specs = cnn.NETWORKS[name][0]()
    res = res or _RES[name]
    params = cnn.init_cnn(jax.random.key(key), specs, 3, res=res)
    return specs, params, res


# ---------------------------------------------------------------------------
# lowering
# ---------------------------------------------------------------------------

def test_lower_produces_unfused_conv_chains():
    """Composite specs lower to their unfused conv chains: fusion is a
    graph rewrite, not a property of the input format."""
    specs, _, _ = _net("mobilenet_v2")
    ir = lower(specs, c_in=3)
    ops = [n.op for n in ir]
    assert ops.count("separable") == 0 and ops.count("inverted_residual") == 0
    convs = [n for n in ir if n.op == "conv2d"]
    # stem + head + 17 blocks x (expand? + dw + pw); ir1 has expand factor 1
    assert len(convs) == 2 + 16 * 3 + 1 * 2
    adds = [n for n in ir if n.op == "add"]
    assert len(adds) == 10            # MBv2's stride-1 same-width blocks
    # every node's inputs are produced earlier (topological order)
    seen = set()
    for n in ir:
        assert all(i in seen for i in n.inputs), n
        seen.add(n.id)


def test_lower_tracks_depthwise_groups():
    specs, _, _ = _net("mobilenet_v1")
    ir = lower(specs, c_in=3)
    dw = next(n for n in ir if n.id == "sep2.dw")
    assert dw.attrs["depthwise"] and dw.attrs["groups"] == 32
    assert dw.attrs["w_path"] == ("sep2", "dw", "w")


def test_infer_shapes_matches_interpreter():
    specs, params, res = _net("squeezenet")
    ir = fuse(lower(specs, c_in=3))
    shapes = infer_shapes(ir, (1, res, res, 3))
    assert shapes[ir[-1].id] == (1, 1000)
    x = jnp.zeros((1, res, res, 3), jnp.float32)
    out = jax.eval_shape(
        lambda x: cnn.cnn_forward(params, x, specs, algorithm="im2col"), x)
    assert shapes[ir[-1].id] == out.shape


# ---------------------------------------------------------------------------
# fusion pattern rewrites
# ---------------------------------------------------------------------------

def test_fuse_rewrites_separable_blocks():
    specs, _, _ = _net("mobilenet_v1")
    ir = fuse(lower(specs, c_in=3))
    seps = [n for n in ir if n.op == "separable"]
    assert len(seps) == 13
    # fused nodes take the origin block's name and splice its edges
    assert {n.id for n in seps} == {f"sep{i}" for i in range(2, 15)}
    assert all(".dw" not in n.id and ".pw" not in n.id for n in ir)


def test_fuse_rewrites_inverted_residuals():
    specs, _, _ = _net("mobilenet_v2")
    ir = fuse(lower(specs, c_in=3))
    irs = [n for n in ir if n.op == "inverted_residual"]
    assert len(irs) == 17
    assert sum(n.attrs["residual"] for n in irs) == 10
    assert sum(1 for n in irs if n.attrs["exp_w"] is None) == 1   # ir1, t=1
    # the linear-projection chains are fully claimed: nothing separable-
    # fusable remains, and no hand-written fusion branch ever ran
    assert not [n for n in ir if n.op == "separable"]


def test_fuse_requires_single_consumer(rng):
    """A depthwise conv feeding TWO pointwise convs must not fuse (the
    z-cache intermediate would be needed twice)."""
    c = 8
    specs = [cnn.Conv("dw", 3, 3, c, groups=c),
             cnn.Concat([[cnn.Conv("pw1", 1, 1, c)],
                         [cnn.Conv("pw2", 1, 1, c)]])]
    ir = fuse(lower(specs, c_in=c))
    assert [n.op for n in ir if n.op != "input"] == \
        ["conv2d", "conv2d", "conv2d", "concat"]
    params = cnn.init_cnn(jax.random.key(0), specs, c, res=16)
    x = jnp.asarray(rng.standard_normal((1, 16, 16, c)), jnp.float32)
    net = compile_network(params, specs, res=16, c_in=c)
    base = cnn.cnn_forward(params, x, specs, algorithm="im2col")
    assert rel_err(net.apply(x), base) < 1e-3


def test_hand_built_ir_residual_flag_is_authoritative(rng):
    """The graph's add (or its absence) decides the skip connection, even
    where shapes would allow one: bind overrides the plan's shape-derived
    residual to match the IR."""
    c = 8
    graph = (
        LayerIR(id="input", op="input"),
        LayerIR(id="dw", op="conv2d", inputs=("input",),
                attrs=dict(kh=3, kw=3, c_out=c, stride=(1, 1),
                           padding="SAME", groups=c, depthwise=True,
                           activation="relu6", w_path=("dw", "w"),
                           b_path=("dw", "b"))),
        LayerIR(id="pw", op="conv2d", inputs=("dw",),
                attrs=dict(kh=1, kw=1, c_out=c, stride=(1, 1),
                           padding="SAME", groups=1, depthwise=False,
                           activation="none", w_path=("pw", "w"),
                           b_path=("pw", "b"))),
    )
    params = {"dw": {"w": jnp.asarray(rng.standard_normal((3, 3, 1, c)) / 9,
                                      jnp.float32),
                     "b": jnp.zeros((c,), jnp.float32)},
              "pw": {"w": jnp.asarray(rng.standard_normal((1, 1, c, c)) / 3,
                                      jnp.float32),
                     "b": jnp.zeros((c,), jnp.float32)}}
    net = compile_network(params, graph, input_shape=(1, 12, 12, c))
    (p,) = [p for p in net.values() if isinstance(p, InvertedResidualPlan)]
    assert p.residual is False        # no add node in the graph
    x = jnp.asarray(rng.standard_normal((1, 12, 12, c)), jnp.float32)
    h = jax.nn.relu6(direct_conv2d(x, params["dw"]["w"], groups=c))
    want = direct_conv2d(h, params["pw"]["w"])
    assert rel_err(net.apply(x), want) < 1e-3


# ---------------------------------------------------------------------------
# placement + whole-zoo routing
# ---------------------------------------------------------------------------

def test_place_falls_back_per_layer():
    """A forced family falls back to im2col exactly on the layers the
    registry's executors don't cover."""
    specs = [cnn.Conv("a", 3, 3, 8),                      # covered
             cnn.Conv("b", 3, 3, 8, stride=3),            # stride 3: not
             cnn.Conv("c", 1, 1, 8)]                      # pointwise: not
    ir = fuse(lower(specs, c_in=4))
    shapes = infer_shapes(ir, (1, 24, 24, 4))
    placements = place(ir, shapes, "winograd")
    assert placements["a"]["algorithm"] == "winograd"
    assert placements["b"]["algorithm"] == "im2col"
    assert placements["c"]["algorithm"] == "im2col"


@pytest.mark.parametrize("net", sorted(cnn.NETWORKS))
def test_whole_zoo_routes_through_compiler(net):
    """Every zoo model compiles through lower -> fuse -> place -> bind and
    the compiled graph's output shape matches the interpreter's."""
    specs, params, res = _net(net)
    plan = compile_network(params, specs, res=res)
    assert plan.out_shape == (1, 1000)
    assert len(plan.describe().splitlines()) == len(plan.plans) + 2


def test_compiled_parity_with_baseline(rng):
    specs, params, res = _net("mobilenet_v2", res=32)
    net = compile_network(params, specs, res=32)
    x = jnp.asarray(rng.standard_normal((1, 32, 32, 3)), jnp.float32)
    base = cnn.cnn_forward(params, x, specs, algorithm="im2col")
    assert rel_err(net.apply(x), base) < 1e-3
    assert rel_err(jax.jit(net.apply)(x), base) < 1e-3


def test_audio_stem_routes_through_compiler(rng):
    from repro import configs as cfglib
    cfg = cfglib.get_smoke_config("whisper_tiny")
    params = audio.init_stem(jax.random.key(0), cfg, n_mels=16)
    mel = jnp.asarray(rng.standard_normal((2, 32, 16)), jnp.float32)
    net = compile_network(params, audio.stem_graph(cfg.d_model),
                          input_shape=mel.shape)
    want = audio.stem(params, mel)
    assert rel_err(net.apply(mel), want) < 1e-4
    assert net.out_shape == (2, 16, cfg.d_model)
    # the stem's stride-2 conv planned onto the polyphase decomposition
    assert net["conv2"].mode == "polyphase"


# ---------------------------------------------------------------------------
# deployment artifact: save/load
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("net,res", [("mobilenet_v2", 32), ("vgg16", 32)])
def test_artifact_round_trip_bitwise(rng, net, res, tmp_path):
    specs, params, _ = _net(net, res=res)
    plan = compile_network(params, specs, res=res)
    x = jnp.asarray(rng.standard_normal((1, res, res, 3)), jnp.float32)
    y_cold = np.asarray(plan.apply(x))
    path = str(tmp_path / "net.npz")
    plan.save(path)
    loaded = NetworkPlan.load(path)
    assert np.array_equal(np.asarray(loaded.apply(x)), y_cold)
    assert plan_cache_info()["artifact_hits"] == 1


def test_artifact_audio_stem_round_trip(rng, tmp_path):
    from repro import configs as cfglib
    cfg = cfglib.get_smoke_config("whisper_tiny")
    params = audio.init_stem(jax.random.key(0), cfg, n_mels=16)
    mel = jnp.asarray(rng.standard_normal((1, 40, 16)), jnp.float32)
    net = compile_network(params, audio.stem_graph(cfg.d_model),
                          input_shape=mel.shape)
    path = str(tmp_path / "stem.npz")
    net.save(path)
    loaded = NetworkPlan.load(path)
    assert np.array_equal(np.asarray(loaded.apply(mel)),
                          np.asarray(net.apply(mel)))


def _tamper(path, **header_updates):
    data = dict(np.load(path, allow_pickle=False))
    header = json.loads(str(data["__header__"][()]))
    header.update(header_updates)
    data["__header__"] = np.array(json.dumps(header))
    np.savez(path, **data)


def test_artifact_mismatch_errors(rng, tmp_path, monkeypatch):
    """Version / registry-fingerprint / dtype / layout mismatches refuse
    with actionable errors (and count as artifact misses)."""
    specs, params, _ = _net("squeezenet", res=32)
    plan = compile_network(params, specs[:2], res=32)   # tiny prefix graph
    path = str(tmp_path / "net.npz")
    plan.save(path)

    _tamper(path, version=99)
    with pytest.raises(ArtifactMismatchError, match="version 99.*recompile"):
        NetworkPlan.load(path)

    plan.save(path)
    monkeypatch.setattr(registry, "fingerprint", lambda: "deadbeef")
    with pytest.raises(ArtifactMismatchError, match="registry.*stale"):
        NetworkPlan.load(path)
    monkeypatch.undo()

    with pytest.raises(ArtifactMismatchError, match="float32.*bfloat16"):
        NetworkPlan.load(path, expect_dtype=jnp.bfloat16)
    with pytest.raises(ArtifactMismatchError, match="layout"):
        NetworkPlan.load(path, expect_layout="NCHW")
    _tamper(path, format="something_else")
    with pytest.raises(ArtifactMismatchError, match="format"):
        NetworkPlan.load(path)
    info = plan_cache_info()
    assert info["artifact_misses"] == 5 and info["artifact_hits"] == 0


def test_compile_artifact_warm_start(rng, tmp_path):
    """compile(..., artifact=path): cold compile + save on the first call
    (an artifact miss), a pure load on the second (a hit)."""
    specs, params, _ = _net("squeezenet", res=32)
    path = str(tmp_path / "net.npz")
    p1 = compile_network(params, specs, res=32, artifact=path)
    assert os.path.exists(path)
    assert plan_cache_info()["artifact_misses"] == 1
    p2 = compile_network(params, specs, res=32, artifact=path)
    assert plan_cache_info()["artifact_hits"] == 1
    x = jnp.asarray(rng.standard_normal((1, 32, 32, 3)), jnp.float32)
    assert np.array_equal(np.asarray(p1.apply(x)), np.asarray(p2.apply(x)))


def test_compile_artifact_rejects_stale_arguments(rng, tmp_path):
    """compile(artifact=) validates the artifact against THIS call: a
    different input shape or retrained weights recompile (one miss each)
    instead of silently serving the old plan."""
    specs = [cnn.Conv("a", 3, 3, 8), cnn.Conv("b", 1, 1, 4)]
    params = cnn.init_cnn(jax.random.key(0), specs, 3, res=32)
    path = str(tmp_path / "net.npz")
    compile_network(params, specs, res=32, artifact=path)         # cold
    assert plan_cache_info()["artifact_misses"] == 1
    p2 = compile_network(params, specs, res=48, artifact=path)    # stale res
    assert p2.input_shape == (1, 48, 48, 3)
    assert plan_cache_info()["artifact_misses"] == 2
    retrained = cnn.init_cnn(jax.random.key(9), specs, 3, res=48)
    p3 = compile_network(retrained, specs, res=48, artifact=path)
    assert plan_cache_info()["artifact_misses"] == 3
    x = jnp.asarray(rng.standard_normal((1, 48, 48, 3)), jnp.float32)
    base = cnn.cnn_forward(retrained, x, specs, algorithm="im2col")
    assert rel_err(p3.apply(x), base) < 1e-3    # the NEW weights are used
    compile_network(retrained, specs, res=48, artifact=path)      # warm now
    info = plan_cache_info()
    assert info["artifact_hits"] == 1 and info["artifact_misses"] == 3
    # an explicit dtype request that differs from the artifact recompiles
    p4 = compile_network(retrained, specs, res=48, dtype=jnp.bfloat16,
                         artifact=path)
    assert p4.dtype == "bfloat16"
    assert plan_cache_info()["artifact_misses"] == 4


def test_compile_artifact_corrupt_file_falls_back(tmp_path):
    """A truncated/garbage artifact must cold-compile (exactly one miss)
    and overwrite itself with a good one -- never crash every warm start
    until someone deletes the file."""
    specs = [cnn.Conv("a", 3, 3, 8)]
    params = cnn.init_cnn(jax.random.key(0), specs, 3, res=16)
    path = str(tmp_path / "net.npz")
    with open(path, "wb") as f:
        f.write(b"definitely not a zip archive")
    compile_network(params, specs, res=16, artifact=path)
    assert plan_cache_info()["artifact_misses"] == 1
    assert NetworkPlan.load(path) is not None   # repaired in place


def test_loaded_plan_performs_zero_filter_transform_ops(rng, tmp_path,
                                                        monkeypatch):
    """The warm path is transform-free, proven two ways: (1) loading never
    reaches the weight-binding chokepoint (every filter arrives in its
    execution domain), and (2) the loaded plan's apply() jaxpr is
    equation-for-equation the cold plan's -- with no raw HWIO filter
    constants left anywhere in it."""
    specs, params, _ = _net("mobilenet_v2", res=32)
    plan = compile_network(params, specs, res=32)
    path = str(tmp_path / "net.npz")
    plan.save(path)

    def boom(*a, **k):
        raise AssertionError("filter transform ran during load()")

    monkeypatch.setattr(planlib, "_bind_weights", boom)
    monkeypatch.setattr(planlib._wg, "transform_filter_2d", boom)
    monkeypatch.setattr(planlib._wg, "strided_phase_filters", boom)
    loaded = NetworkPlan.load(path)
    monkeypatch.undo()

    x = jnp.asarray(rng.standard_normal((1, 32, 32, 3)), jnp.float32)
    j_cold = jax.make_jaxpr(plan.apply)(x)
    j_warm = jax.make_jaxpr(loaded.apply)(x)
    assert [e.primitive.name for e in j_cold.eqns] == \
        [e.primitive.name for e in j_warm.eqns]
    for const in j_warm.consts:
        shape = getattr(const, "shape", ())
        # every MBv2 conv is 3x3 or 1x1: a (3, 3, C, M)-shaped constant
        # would be an untransformed HWIO filter smuggled into the hot path
        assert not (len(shape) == 4 and shape[0] == shape[1] == 3), shape


def test_fresh_process_warm_load_performs_zero_measurements(rng, tmp_path):
    """Acceptance gate for the measured auto_tuned policy: a saved
    auto_tuned NetworkPlan reloads in a FRESH python process with every
    measured per-layer winner intact and ZERO re-measurement -- the
    measured/fallback resolution counters stay at 0 after load()."""
    import subprocess
    import sys

    specs = [cnn.Conv("a", 3, 3, 8), cnn.Conv("b", 3, 3, 16)]
    params = cnn.init_cnn(jax.random.key(0), specs, 3, res=24)
    net = compile_network(params, specs, res=24, algorithm="auto_tuned")
    path = str(tmp_path / "net.npz")
    net.save(path)
    tuned = {k: p.spec for k, p in net.items()
             if getattr(getattr(p, "spec", None), "requested", None)
             == "auto_tuned"}
    assert tuned and all(s.autotune is not None for s in tuned.values())
    winners = {k: s.algorithm for k, s in tuned.items()}

    script = (
        "import json\n"
        "from repro.core.compile import NetworkPlan\n"
        "from repro.core.plan import plan_cache_info\n"
        f"net = NetworkPlan.load({path!r})\n"
        "info = plan_cache_info()\n"
        "tuned = {k: p for k, p in net.items()\n"
        "         if getattr(getattr(p, 'spec', None), 'requested', None)\n"
        "         == 'auto_tuned'}\n"
        "print(json.dumps({\n"
        "    'measured': info['measured'], 'fallback': info['fallback'],\n"
        "    'winners': {k: p.spec.algorithm for k, p in tuned.items()},\n"
        "    'decisions': {k: p.describe()['decision']\n"
        "                  for k, p in tuned.items()}}))\n")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["measured"] == 0 and got["fallback"] == 0
    assert got["winners"] == winners
    assert all(d == "measured" for d in got["decisions"].values())


# ---------------------------------------------------------------------------
# describe(): the per-layer table, same generator as the README table
# ---------------------------------------------------------------------------

def test_describe_uses_the_registry_table_generator(monkeypatch):
    """NetworkPlan.describe() and registry.capability_table() render
    through ONE markdown generator -- the two doc surfaces cannot drift."""
    specs, params, _ = _net("mobilenet_v1_050", res=32)
    net = compile_network(params, specs, res=32)
    calls = []
    real = registry.markdown_table

    def spy(header, rows):
        calls.append(tuple(header))
        return real(header, rows)

    monkeypatch.setattr(registry, "markdown_table", spy)
    table = net.describe()
    registry.capability_table()
    assert len(calls) == 2
    lines = table.splitlines()
    assert lines[1].replace(" ", "").startswith("|---")
    assert any("separable_streamed" in ln or "composed" not in ln
               for ln in lines)
    # one row per bound plan, in graph order, naming the executor
    assert "`winograd_strided`" in table        # the stride-2 stem
    assert "sep2" in table and "fc" not in [r.split("|")[1].strip()
                                            for r in lines[2:]]


def test_describe_reports_fused_modes():
    specs, params, _ = _net("mobilenet_v1_050", res=32)
    net = compile_network(params, specs, res=32,
                          algorithm="pallas_winograd")
    d = net["sep2"].describe()
    assert d["mode"] == "fused_pallas"
    assert d["executor"] == "separable_streamed"
    d3 = net["sep3"].describe()                  # stride-2: composed
    assert d3["mode"] == "composed"
    assert "+" in d3["executor"]


# ---------------------------------------------------------------------------
# deprecation shims
# ---------------------------------------------------------------------------

def test_legacy_entry_points_warn_and_delegate(rng):
    compiler._DEPRECATION_WARNED.clear()
    specs, params, res = _net("squeezenet", res=32)
    with pytest.warns(DeprecationWarning, match="compile"):
        plans = cnn.plan_cnn(params, specs, res=32)
    assert isinstance(plans, NetworkPlan)
    x = jnp.asarray(rng.standard_normal((1, 32, 32, 3)), jnp.float32)
    with pytest.warns(DeprecationWarning, match="NetworkPlan|compile"):
        got = cnn.cnn_forward(params, x, specs, plans=plans)
    assert np.array_equal(np.asarray(got), np.asarray(plans.apply(x)))

    from repro import configs as cfglib
    cfg = cfglib.get_smoke_config("whisper_tiny")
    ap = audio.init_stem(jax.random.key(0), cfg, n_mels=8)
    mel = jnp.asarray(rng.standard_normal((1, 20, 8)), jnp.float32)
    with pytest.warns(DeprecationWarning, match="stem_graph"):
        stem_plans = audio.plan_stem(ap, mel.shape)
    assert isinstance(stem_plans, NetworkPlan)
    assert rel_err(audio.stem(ap, mel, plans=stem_plans),
                   audio.stem(ap, mel)) < 1e-4


def test_legacy_warns_once_per_process():
    compiler._DEPRECATION_WARNED.clear()
    specs, params, _ = _net("squeezenet", res=32)
    with pytest.warns(DeprecationWarning):
        cnn.plan_cnn(params, specs[:1], res=32)
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", DeprecationWarning)
        cnn.plan_cnn(params, specs[:1], res=32)   # second call: silent


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_directory(from_env, tmp_path, monkeypatch):
    """The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR
    says (nothing is set in code), else to the fixed in-checkout path."""
    from repro.runtime import cache

    before = jax.config.jax_compilation_cache_dir
    # setenv records the original value, so the helper's own export of the
    # default is undone after the test; an empty value reads as unset.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path) if from_env else "")
    try:
        got = cache.enable_compile_cache()
        if from_env:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert got == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert os.environ["JAX_COMPILATION_CACHE_DIR"] == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
