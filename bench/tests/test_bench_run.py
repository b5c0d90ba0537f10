"""A whole run of the harness on the CPU at a small size, with the look
for a chip skipped: a sound program comes out correct, and a fault planted
where the answers are produced comes out not correct."""

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import flops, manifest  # noqa: E402
from bench import run as bench_run  # noqa: E402

RES = 32
TRAFFIC = {"kind": "closed", "in_flight": 4, "pool": 8, "buckets": [2],
           "warm_requests": 4}


def small(config: str, **over) -> dict:
    """The configuration at a small resolution and on the plain XLA path,
    which the CPU runs in seconds."""
    cfg = dict(manifest.config(config), res=RES, algorithm="auto")
    cfg.update(over)
    return cfg


CACHE_KNOBS = ("jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes",
               "jax_compilation_cache_max_size")


@pytest.fixture
def cpu_run(monkeypatch, tmp_path):
    """Runs of the harness with the look for a chip skipped and the
    persistent compile cache left as this process has it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(bench_run, "require_chips", lambda chips: (
        jax.devices(), flops.peaks("TPU v5 lite")))
    knobs = {k: getattr(jax.config, k) for k in CACHE_KNOBS}

    def go(cfg, traffic=TRAFFIC):
        return bench_run.run("small", 2**31 + 7, 0.5, False,
                             cell_files=(cfg, traffic))[0]
    yield go
    for k, v in knobs.items():
        jax.config.update(k, v)


def _dispatch_altering(alter):
    """Server._dispatch with `alter` applied to the logits it returns."""
    from repro.runtime.serve import Server
    orig = Server._dispatch

    def dispatch(self, bucket, X):
        y, layer_times = orig(self, bucket, X)
        return alter(np.array(y)), layer_times
    return dispatch


def test_sound_run_is_correct(cpu_run):
    res = cpu_run(small("mbv2_224"))
    assert res["correct"], res["checks"]
    assert res["checks"]["logit_err"]["value"] < 1e-4
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert list(res)[-1] == "checks"


def _swap_rows(y):
    y[[0, 1]] = y[[1, 0]]
    return y


def _bump_one_logit(y):
    y[0, 0] += 0.5 * np.abs(y[0]).max()
    return y


@pytest.mark.parametrize("alter", [_swap_rows, _bump_one_logit],
                         ids=["answers_swapped", "answer_altered"])
def test_altered_answers_are_not_correct(cpu_run, monkeypatch, alter):
    from repro.runtime.serve import Server
    monkeypatch.setattr(Server, "_dispatch", _dispatch_altering(alter))
    res = cpu_run(small("vgg16_224"))
    assert not res["correct"]
    assert res["checks"]["logit_err"]["value"] > \
        res["checks"]["logit_err"]["limit"]


@pytest.mark.parametrize("config", ["mbv2_224", "vgg16_224"])
def test_control_is_not_correct(cpu_run, monkeypatch, config):
    """The control: the plain reference computed with every product's
    operands in float8 (one step below the bf16 products of the chip's
    default float32 matmul), put in the program's place."""
    import jax.numpy as jnp
    from repro.runtime.serve import Server
    cfg = small(config)
    mod = __import__(f"bench.reference.{cfg['reference']}",
                     fromlist=["forward"])
    fp8 = jax.jit(lambda p, x: mod.forward(p, x, cfg, jnp.float8_e4m3fn))

    def dispatch(self, bucket, X):
        return fp8(self.params, jnp.asarray(X)), {}
    monkeypatch.setattr(Server, "_dispatch", dispatch)
    res = cpu_run(cfg)
    assert not res["correct"]
    assert res["checks"]["logit_err"]["value"] > \
        res["checks"]["logit_err"]["limit"]
