"""Sharding rules + ZeRO + elastic restore (subprocess: needs >1 device)."""
import subprocess
import sys
import textwrap



def _run(src: str, devices: int = 8, timeout: int = 600):
    prog = ("import os\n"
            f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
            + textwrap.dedent(src))
    proc = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                          text=True, timeout=timeout,
                          env={**__import__('os').environ,
                               "PYTHONPATH": "src"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_param_specs_follow_rules():
    out = _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.parallel.sharding import spec_for_param, zero_spec
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    # attention projections: col/row parallel
    assert spec_for_param("layers/attn/wq", (16, 64, 128), mesh) == P(None, None, "model")
    assert spec_for_param("layers/attn/wo", (16, 128, 64), mesh) == P(None, "model", None)
    # vocab-parallel embeddings
    assert spec_for_param("embed", (1024, 64), mesh) == P("model", None)
    # non-divisible dims drop the axis
    assert spec_for_param("layers/attn/wq", (16, 64, 129), mesh) == P(None, None, None)
    # experts: (data x model) when divisible, else model
    assert spec_for_param("moe_layers/moe/wg", (8, 8, 64, 32), mesh) == \
        P(None, ("data", "model"), None, None)
    assert spec_for_param("moe_layers/moe/wg", (8, 4, 64, 32), mesh) == \
        P(None, "model", None, None)
    # norms replicate
    assert spec_for_param("layers/ln1", (16, 64), mesh) == P()
    # ZeRO adds unused dp axes only
    assert zero_spec(P(None, "model"), (8, 64), mesh) == P("data", "model")
    assert zero_spec(P(("data", "model"), None), (8, 64), mesh) == \
        P(("data", "model"), None)
    print("RULES-OK")
    """)
    assert "RULES-OK" in out


def test_elastic_restore_onto_smaller_mesh(tmp_path):
    """Checkpoint on an 8-device (2x4) mesh, restore onto 4 devices (2x2)."""
    out = _run(f"""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import save_sync, restore, latest_step
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    w = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)
    w = jax.device_put(w, NamedSharding(mesh, P("data", "model")))
    save_sync(r"{tmp_path}", 5, {{"w": w}})

    # elastic restart: the new "cluster" is a 2x2 mesh over 4 of the devices
    small = jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    target = jax.ShapeDtypeStruct(
        (8, 16), jnp.float32,
        sharding=NamedSharding(small, P("data", "model")))
    got = restore(r"{tmp_path}", 5, {{"w": target}})
    np.testing.assert_array_equal(np.asarray(got["w"]),
                                  np.arange(8 * 16).reshape(8, 16))
    assert got["w"].sharding.mesh.shape["model"] == 2
    print("ELASTIC-OK")
    """)
    assert "ELASTIC-OK" in out


def test_compressed_allreduce_matches_mean():
    """int8 reduce-scatter/all-gather grad exchange ~= exact mean (shard_map)."""
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.parallel.compression import compressed_psum_grads
    mesh = jax.make_mesh((4,), ("data",))
    g = jax.random.normal(jax.random.PRNGKey(0), (4, 1024)) * 2.0

    def region(gs):
        return compressed_psum_grads({"g": gs[0]}, mesh, axis="data")["g"]

    out = jax.jit(jax.shard_map(region, mesh=mesh, in_specs=P("data", None),
                                out_specs=P(None), check_vma=False))(g)
    want = g.mean(0)
    err = float(jnp.max(jnp.abs(out - want)))
    scale = float(jnp.max(jnp.abs(g))) / 127
    assert err <= 2 * scale + 1e-6, (err, scale)
    print("COMPRESS-OK")
    """)
    assert "COMPRESS-OK" in out
