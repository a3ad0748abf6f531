"""Hetero-DP scheduler, gradient compression, and sharding-rule tests."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.hetero import (BatchSplit, HeteroBatchScheduler,
                                      PodProfile)


# -------------------------------------------------------------- hetero DP --

PODS = [
    PodProfile("pod0", chips=256, peak_flops=197e12, grain=16),
    PodProfile("pod1", chips=256, peak_flops=197e12, grain=16),
]


def test_equal_pods_equal_split():
    s = HeteroBatchScheduler(PODS, flops_per_token=6 * 12e9, seq_len=4096)
    split = s.plan(256)
    assert sum(split.sizes) == 256
    assert split.sizes[0] == split.sizes[1] == 128
    assert all(x % 16 == 0 for x in split.sizes)


def test_derated_pod_gets_less():
    pods = [PODS[0], PodProfile("slow", 256, 197e12, derate=0.5, grain=16)]
    s = HeteroBatchScheduler(pods, flops_per_token=6 * 12e9, seq_len=4096)
    split = s.plan(256)
    assert sum(split.sizes) == 256
    assert split.sizes[0] > split.sizes[1]
    assert split.sizes[0] / max(split.sizes[1], 1) == pytest.approx(2.0,
                                                                    rel=0.35)


def test_dynamic_straggler_rebalance():
    s = HeteroBatchScheduler(PODS, flops_per_token=6 * 12e9, seq_len=4096,
                             dynamic=True)
    split0 = s.plan(256)
    # pod1 starts straggling 3x: feed observations of measured step times
    for step in range(4):
        t0 = s.devices[0].compute(split0.sizes[0] * 4096)
        s.observe(0, split0.sizes[0], t0)
        s.observe(1, split0.sizes[1], 3.0 * t0 * (1 + 0.01 * step))
    split1 = s.plan(256)
    assert split1.sizes[0] > 2 * split1.sizes[1]
    assert sum(split1.sizes) == 256
    # imbalance estimate should be small after rebalancing
    assert s.imbalance(split1) < 0.35


def test_split_grain_and_conservation_property():
    rng = np.random.default_rng(0)
    for _ in range(20):
        n_pods = rng.integers(1, 5)
        pods = [PodProfile(f"p{i}", 256, 197e12,
                           derate=float(rng.uniform(0.3, 1.0)), grain=8)
                for i in range(n_pods)]
        s = HeteroBatchScheduler(pods, flops_per_token=1e9, seq_len=1024,
                                 dynamic=False)
        gb = int(rng.integers(1, 40)) * 8
        split = s.plan(gb)
        assert sum(split.sizes) == gb
        assert all(x >= 0 for x in split.sizes)


# ------------------------------------------------- compressed collectives --

def test_int8_quantization_error_bounded():
    from repro.distributed.collectives import dequantize_int8, quantize_int8
    x = jax.random.normal(jax.random.PRNGKey(0), (1024,)) * 0.01
    q, scale = quantize_int8(x, jax.random.PRNGKey(1))
    x2 = dequantize_int8(q, scale, jnp.float32)
    # max error is one quantization step
    assert float(jnp.max(jnp.abs(x2 - x))) <= float(scale) * 1.01


def test_int8_stochastic_rounding_unbiased():
    from repro.distributed.collectives import dequantize_int8, quantize_int8
    x = jnp.full((4096,), 0.3e-2)
    errs = []
    for i in range(20):
        q, s = quantize_int8(x, jax.random.PRNGKey(i))
        errs.append(float(jnp.mean(dequantize_int8(q, s, jnp.float32) - x)))
    assert abs(np.mean(errs)) < 5e-6  # zero-mean across keys


def test_compressed_psum_subprocess():
    """shard_map psum with int8 compression on 4 forced host devices."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import compressed_psum_mean
mesh = jax.make_mesh((4,), ("pod",), devices=jax.devices())
x = jnp.arange(16, dtype=jnp.float32).reshape(4, 4) / 100.0

def body(xl, key):
    return compressed_psum_mean(xl[0], "pod", key, mode="int8")[None]

out = jax.jit(jax.shard_map(body, mesh=mesh,
    in_specs=(P("pod", None), P()), out_specs=P("pod", None),
    check_vma=False))(x, jax.random.PRNGKey(0))
expected = x.mean(axis=0)
err = float(jnp.max(jnp.abs(out - expected[None])))
assert err < 2e-3, err
print("OK", err)
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**__import__("os").environ,
                                        "PYTHONPATH": "src"},
                       cwd=__import__("pathlib").Path(__file__).parent.parent)
    assert "OK" in r.stdout, r.stderr[-2000:]


# ------------------------------------------------------------- shardings --

def test_sharding_rules_subprocess():
    """Param spec rules on a (2,2,2) mesh: TP/FSDP axes land where expected."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from jax.sharding import PartitionSpec as P
from repro.configs import get_tiny_config
from repro.launch.specs import param_specs
from repro.distributed.sharding import param_shardings
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     devices=jax.devices())
cfg = get_tiny_config("stablelm-12b")
specs = param_specs(cfg)
sh = param_shardings(specs, mesh)
assert sh["embed"].spec == P("model", None), sh["embed"].spec
assert sh["layers"]["attn"]["wq"].spec == P(None, "data", "model", None)
# tiny cfg: kv=2 divides the size-2 model axis, so KH itself shards
assert sh["layers"]["attn"]["wk"].spec == P(None, "data", "model", None)
assert sh["layers"]["mlp"]["wi"].spec == P(None, "data", "model")
assert all(a is None for a in sh["layers"]["ln1"]["scale"].spec)
cfg2 = get_tiny_config("dbrx-132b")
sh2 = param_shardings(param_specs(cfg2), mesh)
assert sh2["layers"]["moe"]["w_in"].spec == P(None, "model", "data", None)
print("OK")
"""
    import os
    import pathlib
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": "src"},
                       cwd=pathlib.Path(__file__).parent.parent)
    assert "OK" in r.stdout, r.stderr[-2000:]
