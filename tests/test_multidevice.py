"""True multi-device SPMD execution (not just lowering): run sharded train
and decode steps on 8 forced host devices in a subprocess (the device count
locks at first jax init, so the main test process stays single-device)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.distributed.sharding import make_rules, use_mesh_rules
    from repro.launch.mesh import make_mesh
    from repro.models.common import get_family
    from repro.nn.param import init_params
    from repro.train.steps import TrainConfig, init_state, make_train_step

    assert len(jax.devices()) == 8, jax.devices()
    mesh = make_mesh((4, 2), ("data", "model"))
    rules = make_rules()

    cfg = get_config("{arch}", smoke=True)
    fam = get_family(cfg)

    with use_mesh_rules(mesh, rules):
        tmpl = fam.template(cfg)
        sh = rules.param_sharding(tmpl, mesh)
        params = init_params(tmpl, jax.random.key(0))
        params = jax.tree.map(jax.device_put, params, sh)
        state = init_state(cfg, params)

        B, S = 8, 32
        tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
        from jax.sharding import NamedSharding
        tsh = NamedSharding(mesh, rules.pspec(("batch", "seq"), (B, S), mesh))
        batch = {{
            "tokens": jax.device_put(tokens, tsh),
            "labels": jax.device_put(jnp.roll(tokens, -1, 1), tsh),
        }}
        step = jax.jit(make_train_step(cfg, TrainConfig(accum_steps=2)),
                       donate_argnums=(0,))
        l0 = None
        for _ in range(4):
            state, m = step(state, batch)
            loss = float(m["loss"])
            l0 = l0 if l0 is not None else loss
        assert np.isfinite(loss), loss
        assert loss < l0, (l0, loss)  # overfits the fixed batch

        # sharded decode
        cache = fam.init_cache(cfg, B, S)
        csh = {{k: NamedSharding(mesh, rules.pspec(fam.cache_logical_axes(cfg)[k],
                                                   v.shape, mesh))
               for k, v in cache.items()}}
        cache = jax.tree.map(jax.device_put, cache, csh)
        dec = jax.jit(lambda p, c, t, q: fam.decode_step(p, cfg, c, t, q),
                      donate_argnums=(1,))
        logits, cache = dec(state["params"], cache, tokens[:, :1], jnp.int32(0))
        assert bool(jnp.isfinite(logits).all())
        print("MULTIDEVICE_OK", loss)
""")


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "rwkv6-3b"])
def test_sharded_train_and_decode_run_on_8_devices(arch):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT.format(arch=arch)],
        capture_output=True, text=True, timeout=420, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "MULTIDEVICE_OK" in out.stdout
