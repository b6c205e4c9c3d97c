"""Compile-only rehearsal of a chain cell's executable for a described
TPU v5e, at the block shapes the cell draws (its pattern is fixed, so any
``--seed`` gives them); no chip.

  JAX_PLATFORMS=cpu python bench/tools/rehearse.py --workload <cell> [--seed n]

Prints the compiled executable's memory analysis (arguments, outputs,
temporaries) per device. The program picks its kernel route from
``jax.default_backend()``, which is the CPU here, so the rehearsal
reports "tpu" to it while tracing.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import chainlib, spec
    bench = spec.benchmark()
    cell = spec.cell(args.workload, bench)
    C, T = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    jax.config.update("jax_enable_compilation_cache", False)
    jax.default_backend = lambda: "tpu"          # the program's route query

    from repro.core import gibbs as GIBBS
    from repro.core.posterior import RowGaussians

    blk = chainlib.draw(C, T, args.seed)
    m_r, m_c = (-(-d // 8) * 8 for d in chainlib.max_degrees(blk))
    n_t = len(blk.test_rows)
    N, D, K = blk.n_rows, blk.n_cols, C["K"]
    print(f"{args.workload}: block {T['block']}, {N} x {D}, planes "
          f"M={m_r} / {m_c}, {n_t} test, K={K}")
    cfg = chainlib.chain_config(C, T)
    fixed = T["priors"] == "fixed"
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    f32, i32 = jnp.float32, jnp.int32
    sh = SingleDeviceSharding(topo.devices[0])
    S = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=sh)
    csr_r = (S((N, m_r), i32), S((N, m_r), f32), S((N, m_r), f32))
    csr_c = (S((D, m_c), i32), S((D, m_c), f32), S((D, m_c), f32))
    pr = lambda n: RowGaussians(S((n, K), f32), S((n, K, K), f32))
    up, vp = (pr(N), pr(D)) if fixed else (None, None)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=sh)
    lowered = GIBBS._run_gibbs_jit.trace(
        key, csr_r, csr_c, S((n_t,), i32), S((n_t,), i32),
        cfg._replace(n_samples=0, burnin=0, phase_bc_samples=None),
        D, N, S((), i32), S((), i32), up, vp, S((N, K), f32),
        S((D, K), f32)).lower()
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    print(ma)
    tot = (ma.argument_size_in_bytes + ma.output_size_in_bytes
           + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(f"bytes: arguments {ma.argument_size_in_bytes}, outputs "
          f"{ma.output_size_in_bytes}, temporaries {ma.temp_size_in_bytes},"
          f" aliased {ma.alias_size_in_bytes}; total {tot} "
          f"({tot / 2**30:.2f} GiB)")
    print("kernels in executable:",
          compiled.as_text().count("tpu_custom_call"))


if __name__ == "__main__":
    main()
