"""Integration tests: mini dry-run in a subprocess (8 fake devices), int8
KV-cache decode quality, checkpoint roundtrip, optimizer sanity."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]

MINI_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import get_config, TrainConfig, InputShape
    from repro.models import steps as STEPS
    from repro.sharding import partitioning as PART
    from repro.roofline import jaxpr_cost as JC, analysis as ROOF

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    cfg = get_config("llama3_8b").smoke_variant()
    shape = InputShape("mini_train", 128, 8, "train")
    named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                   is_leaf=lambda x: isinstance(x, P))
    params_s = STEPS.params_specs(cfg)
    p_sh = named(PART.param_specs(params_s, cfg, mesh))
    batch_s = STEPS.batch_specs(cfg, shape)
    opt_s = STEPS.opt_specs(cfg)
    b_sh = named(PART.batch_specs(batch_s, cfg, shape, mesh))
    o_sh = named(PART.opt_specs(opt_s, params_s, cfg, mesh))
    step = STEPS.make_train_step(cfg, TrainConfig(microbatches=2))
    with jax.set_mesh(mesh):
        tr = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, None),
                     donate_argnums=(0, 1)).trace(params_s, opt_s, batch_s)
        jc = JC.jaxpr_cost(tr.jaxpr)
        compiled = tr.lower().compile()
    terms = ROOF.terms_from(jc, compiled.as_text(), 8)
    print(json.dumps({"flops": terms.flops, "coll": terms.coll_bytes,
                      "dominant": terms.dominant}))
""")


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", MINI_DRYRUN], env=env,
                         capture_output=True, text=True, timeout=400)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 0
    assert rec["coll"] > 0          # TP attention/mlp must emit collectives


def test_int8_kv_decode_close_to_bf16():
    """Quantized-cache decode must track the full-precision logits."""
    import dataclasses
    from repro.configs.base import get_config
    from repro.models import model as MODEL
    from repro.models.kvcache import serve_cache_init

    cfg = dataclasses.replace(get_config("llama3_8b").smoke_variant(),
                              dtype="float32")
    params = MODEL.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (1, 10), 0, cfg.vocab_size)

    def run(quant):
        cache = serve_cache_init(cfg, 1, 64, dtype=jnp.float32,
                                 kv_quant=quant)
        logits = None
        for i in range(10):
            logits, cache = MODEL.decode_step(params, cfg, cache,
                                              toks[:, i:i + 1])
        return np.asarray(logits)

    full = run(False)
    quant = run(True)
    # int8 cache: small logit error, same argmax almost surely
    assert np.abs(full - quant).max() < 0.15, np.abs(full - quant).max()
    assert full.argmax() == quant.argmax()


def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import ckpt
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    ckpt.save(tmp_path / "t", tree, step=7, extra={"note": "x"})
    like = jax.tree.map(jnp.zeros_like, tree)
    back = ckpt.restore(tmp_path / "t", like)
    np.testing.assert_allclose(np.asarray(back["a"]), np.asarray(tree["a"]))
    assert ckpt.manifest(tmp_path / "t")["step"] == 7


def test_adamw_converges_quadratic():
    from repro.configs.base import TrainConfig
    from repro.optim import adamw
    params = {"w": jnp.asarray([3.0, -2.0])}
    opt = adamw.init(params)
    tcfg = TrainConfig(learning_rate=0.1, weight_decay=0.0)
    for _ in range(200):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, opt = adamw.apply(params, g, opt, tcfg, 0.1)
    assert float(jnp.abs(params["w"]).max()) < 1e-2
