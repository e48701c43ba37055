"""Distribution-path tests.  These run in SUBPROCESSES because they need
``--xla_force_host_platform_device_count`` which must be set before jax
initialises (and must NOT leak into the rest of the suite)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_shardmap_moe_matches_single_device():
    """Explicit expert-parallel MoE (shard_map + all-to-all) must equal the
    single-device grouped-vmap path bit-for-bit."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.models.moe import moe_apply
        from repro.models.moe_dist import moe_apply_auto
        from repro.models.configs import MoEConfig
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        T, d, E, f = 256, 32, 8, 48
        m = MoEConfig(num_experts=E, top_k=2, d_ff_expert=f,
                      capacity_factor=8.0)
        params = {"router": jax.random.normal(ks[0], (d, E)) * 0.02,
                  "w_gate": jax.random.normal(ks[1], (E, d, f)) * 0.05,
                  "w_up": jax.random.normal(ks[2], (E, d, f)) * 0.05,
                  "w_down": jax.random.normal(ks[3], (E, f, d)) * 0.05}
        x = jax.random.normal(ks[4], (T, d))
        y_ref, aux_ref = moe_apply(x, params, m)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        from repro.distributed.sharding import mesh_context
        with mesh_context(mesh):
            y, aux = jax.jit(lambda a, b: moe_apply_auto(a, b, m,
                                                         fsdp=False))(x, params)
        err = float(jnp.abs(y - y_ref).max())
        assert err < 1e-5, err
        assert abs(float(aux - aux_ref)) < 1e-6
        print("OK", err)
    """)
    assert "OK" in out


def test_unified_forward_under_mesh_matches_single_device():
    """The whole unified forward (reduced MoE+attn arch) sharded over a 2x4
    mesh must match the unsharded result."""
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        import dataclasses
        from repro.configs import get_reduced
        from repro.models.schema import init_params
        from repro.models.model import unified_forward, init_cache
        from repro.models.stream import PFBatch, UnifiedBatch
        cfg = get_reduced("llama4-maverick-400b-a17b")
        # generous capacity: the shard_map path packs per LOCAL shard, so a
        # tight capacity factor drops different tokens than the global pack
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=8.0))
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)
        pf = PFBatch(tokens=toks, length=jnp.full((4,), 16),
                     adapter=jnp.full((4,), -1))
        ref = unified_forward(cfg, params, UnifiedBatch(pf=pf),
                              cache=init_cache(cfg, 4, 32))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        from repro.distributed.sharding import mesh_context
        with mesh_context(mesh):
            got = jax.jit(lambda p, b, c: unified_forward(cfg, p, b, c))(
                params, UnifiedBatch(pf=pf), init_cache(cfg, 4, 32))
        err = float(jnp.abs(got.pf_logits - ref.pf_logits).max())
        assert err < 2e-4, err
        print("OK", err)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_dryrun_lowers_whisper_long_context():
    """End-to-end dry-run smoke: lower+compile one real combo on the
    512-device production mesh inside a subprocess."""
    out = _run("""
        import sys
        sys.argv = ["dryrun", "--arch", "whisper-base", "--shape",
                    "long_500k", "--out", "/tmp/dryrun_test_ci"]
        import shutil; shutil.rmtree("/tmp/dryrun_test_ci", ignore_errors=True)
        import runpy
        try:
            runpy.run_module("repro.launch.dryrun", run_name="__main__")
        except SystemExit as e:
            assert e.code in (0, None), e.code
        import json, glob
        rec = json.load(open(glob.glob("/tmp/dryrun_test_ci/*.json")[0]))
        assert rec["status"] == "ok", rec
        assert rec["chips"] == 256
        print("OK compile_s", rec["compile_s"])
    """, devices=512, timeout=560)
    assert "OK" in out
