"""Multi-device behaviors (8 host CPU devices via subprocess): MoE all_to_all
path vs oracle, flash-decode partial-softmax combine, elastic checkpoint
reshard. Subprocess keeps the main test session at 1 device."""
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    import sys; sys.path.insert(0, "src")
    from repro.models.moe import MoEDims, moe_ffn
    from repro.models.attention import decode_attention, flash_decode_sharded
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    E, K, d, f = 8, 2, 16, 32
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    params = {"router": jax.random.normal(ks[0], (d, E)) * 0.1,
              "w1": jax.random.normal(ks[1], (E, d, f)) * 0.1,
              "w3": jax.random.normal(ks[2], (E, d, f)) * 0.1,
              "w2": jax.random.normal(ks[3], (E, f, d)) * 0.1}
    x = jax.random.normal(ks[4], (4, 16, d))
    dims = MoEDims(E, K, capacity_factor=8.0)
    xt = x.reshape(-1, d)
    tl, ti = jax.lax.top_k(xt @ params["router"], K)
    w = jax.nn.softmax(tl, -1)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xt, params["w1"])) * \\
        jnp.einsum("td,edf->tef", xt, params["w3"])
    y_all = jnp.einsum("tef,efd->ted", h, params["w2"])
    ref = (jnp.take_along_axis(y_all, ti[:, :, None], 1) * w[..., None]).sum(1).reshape(x.shape)
    with jax.set_mesh(mesh):
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        ps = {k: jax.device_put(v, NamedSharding(mesh, P("model", None, None))
                                if k != "router" else NamedSharding(mesh, P()))
              for k, v in params.items()}
        for mode in ("train", "decode"):
            out = jax.jit(lambda a, b: moe_ffn(a, b, dims, mesh, mode=mode))(xs, ps)
            err = float(jnp.abs(out - ref).max())
            assert err < 2e-2, (mode, err)
    print("moe-8dev ok")

    B, S, H, KV, hd = 2, 64, 4, 4, 8
    q = jax.random.normal(key, (B, 1, H, hd))
    kc = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd))
    vc = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd))
    clen = jnp.asarray(50, jnp.int32)
    ref2 = decode_attention(q, kc, vc, clen)
    seq_mesh = make_mesh((1, 8), ("data", "model"))
    with jax.set_mesh(seq_mesh):
        kcs = jax.device_put(kc, NamedSharding(seq_mesh, P(None, "model", None, None)))
        vcs = jax.device_put(vc, NamedSharding(seq_mesh, P(None, "model", None, None)))
        out2 = jax.jit(lambda a, b, c, l: flash_decode_sharded(
            a, b, c, l, mesh=seq_mesh, seq_axis="model"))(q, kcs, vcs, clen)
    err = float(jnp.abs(out2 - ref2).max())
    assert err < 1e-4, err
    print("flash-decode ok")

    # elastic checkpoint reshard: save sharded one way, restore another
    from repro.checkpoint import ckpt
    import tempfile
    tree = {"w": jax.device_put(jnp.arange(64.0).reshape(8, 8),
                                NamedSharding(mesh, P("data", None)))}
    with tempfile.TemporaryDirectory() as td:
        ckpt.save(td, 1, tree)
        new_sh = {"w": NamedSharding(mesh, P(None, "model"))}
        restored, _ = ckpt.restore(td, 1, tree, shardings=new_sh)
        assert restored["w"].sharding == new_sh["w"]
        np.testing.assert_allclose(np.asarray(restored["w"]),
                                   np.asarray(tree["w"]))
    print("reshard ok")
""")


@pytest.mark.slow
def test_multidevice_behaviors():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=420, cwd=".")
    assert r.returncode == 0, r.stdout + r.stderr
    for tag in ("moe-8dev ok", "flash-decode ok", "reshard ok"):
        assert tag in r.stdout
