"""Distributed BMF inside one block (paper ref [16], Fig. 2 pattern):
rows of U sharded over 8 devices, V replicated with psum'd sufficient
statistics — the 'limited communication' structure. The chain is the one
chain body every executor runs, with its factor samplers data-sharded
(``distributed.run_gibbs_group`` on an 8-device group).

NOTE: must run as its own process (device count is fixed at first jax use).

  PYTHONPATH=src python examples/distributed_block.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import bmf as BMF  # noqa: E402
from repro.core import distributed as DIST  # noqa: E402
from repro.core import gibbs as GIBBS  # noqa: E402
from repro.core.topology import Topology  # noqa: E402
from repro.data import synthetic as SYN  # noqa: E402
from repro.data.sparse import coo_to_padded_csr, train_test_split  # noqa: E402


def main():
    topo = Topology(block=1, data=8)
    coo, preset = SYN.generate("movielens", seed=0)
    train, test = train_test_split(coo, 0.1, seed=1)
    csr_r = coo_to_padded_csr(train)
    csr_c = coo_to_padded_csr(train.transpose())
    cfg = BMF.BMFConfig(K=preset.K, n_samples=40, burnin=15)

    # per-shard transposed planes: each shard's items x its local users,
    # the inputs of the psum'd partial item statistics
    S = topo.data
    n_rows_pad = -(-train.n_rows // S) * S
    csrt = DIST.shard_transposed_planes(train.row, train.col, train.val, S,
                                        n_rows_pad, train.n_cols,
                                        int(csr_c.idx.shape[1]))
    res = DIST.run_gibbs_group(
        jax.random.key(0), csr_r, csr_c,
        jnp.asarray(test.row), jnp.asarray(test.col), cfg, topo,
        comm="psum", csrt=csrt)
    rmse = float(GIBBS.rmse_from_acc(res.acc, jnp.asarray(test.val)))

    comm = DIST.sweep_comm_bytes(train.n_cols, cfg.K)
    print(f"8-way distributed Gibbs: RMSE={rmse:.4f}")
    print(f"communication per sweep: {comm/1e3:.1f} KB "
          f"(D*(K^2+K) floats — independent of the {train.nnz} ratings)")
    base = float(np.sqrt(np.mean((test.val - train.val.mean()) ** 2)))
    assert rmse < base
    print("OK")


if __name__ == "__main__":
    main()
