"""Pipeline parallelism (GPipe over the pod axis): exact fwd/bwd
equivalence vs the sequential stack, on a REAL 2-device mesh
(subprocess, dryrun-only XLA flag rule)."""

import subprocess
import sys
import textwrap

import pytest


def run_sub(code: str):
    pre = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax
        import jax.numpy as jnp
        import numpy as np
    """)
    r = subprocess.run(
        [sys.executable, "-c", pre + textwrap.dedent(code)],
        capture_output=True, text=True, timeout=540,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
             "JAX_PLATFORMS": "cpu"})  # skip the TPU-probe stall
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


PIPELINE_BODY = """
    from repro.runtime.pipeline import (pipeline_apply, sequential_apply,
                                        split_stages, plan_pipeline)

    # a toy residual block stack: (L, d, d) weights
    L, d, mb, n_micro, S = 8, 16, 2, 4, 2
    key = jax.random.key(0)
    params = {"w": jax.random.normal(key, (L, d, d)) * 0.1,
              "b": jnp.zeros((L, d))}

    def stage_fn(p, x):
        def layer(xc, i):
            return xc + jnp.tanh(xc @ p["w"][i] + p["b"][i]), None
        y, _ = jax.lax.scan(layer, x, jnp.arange(p["w"].shape[0]))
        return y

    stages = split_stages(params, S)
    x = jax.random.normal(jax.random.key(1), (n_micro, mb, 4, d))

    mesh = jax.make_mesh((S,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    ref = sequential_apply(stage_fn, stages, x)
"""


@pytest.mark.slow
def test_pipeline_forward_matches_sequential():
    out = run_sub(PIPELINE_BODY + """
    got = pipeline_apply(stage_fn, stages, x, mesh=mesh)
    err = float(jnp.abs(got - ref).max())
    print("PP-FWD err", err)
    assert err < 1e-5
    """)
    assert "PP-FWD" in out


@pytest.mark.slow
def test_pipeline_gradients_match_sequential():
    out = run_sub(PIPELINE_BODY + """
    def loss_pp(p):
        st = split_stages(p, S)
        return (pipeline_apply(stage_fn, st, x, mesh=mesh) ** 2).sum()

    def loss_seq(p):
        st = split_stages(p, S)
        return (sequential_apply(stage_fn, st, x) ** 2).sum()

    g1 = jax.grad(loss_pp)(params)
    g2 = jax.grad(loss_seq)(params)
    errs = [float(jnp.abs(a - b).max())
            for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2))]
    print("PP-GRAD errs", errs)
    assert all(e < 1e-4 for e in errs)
    """)
    assert "PP-GRAD" in out


def test_plan_pipeline():
    from repro.runtime.pipeline import plan_pipeline
    # bubble rule: >= 4x stages when batch allows
    assert plan_pipeline(32, 2, 1e6, 1e9) == 8
    # memory-constrained: enough microbatches to fit
    n = plan_pipeline(32, 2, 1e9, 4e9)
    assert n >= 8 and 32 % n == 0
    # tiny batch: capped
    assert plan_pipeline(2, 2, 1e6, 1e9) == 2


def test_split_stages_shapes():
    import jax.numpy as jnp
    from repro.runtime.pipeline import split_stages
    tree = {"w": jnp.zeros((8, 3, 3)), "b": jnp.zeros((8, 3))}
    st = split_stages(tree, 4)
    assert st["w"].shape == (4, 2, 3, 3)
    assert st["b"].shape == (4, 2, 3)
