"""Distributed tabular training through horovod_tpu.spark.run.

Reference analog: examples/keras_spark_rossmann.py — the shape of it: a
feature-engineered tabular regression trained data-parallel on Spark
executors, results gathered on the driver. The Rossmann CSVs are not
shippable, so the features are synthetic with a known ground truth; the
Spark mechanics (rank assignment by host hash, in-task hvd.init,
rank-ordered result collection) are exactly what the reference exercises.

Runs on a real pyspark cluster when one is importable; otherwise
backend="local" spawns one process per rank with the same protocol.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import horovod_tpu.spark


def train(num_features, steps):
    """Runs inside each Spark task / local rank process."""
    import jax
    # Spark executors are CPU ranks (as in the reference's Rossmann
    # example): select the backend explicitly, whatever JAX_PLATFORMS the
    # executor inherited.
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    hvd.init()
    mesh = hvd.mesh()
    rng = np.random.default_rng(7)  # same data every rank; sharded below
    true_w = rng.standard_normal((num_features, 1)).astype(np.float32)
    X = rng.standard_normal((256, num_features)).astype(np.float32)
    y = X @ true_w + 0.01 * rng.standard_normal((256, 1)).astype(np.float32)

    w = jnp.zeros((num_features, 1))
    w = hvd.broadcast_parameters(w, root_rank=0)
    tx = hvd.DistributedOptimizer(optax.adam(0.05))
    opt_state = tx.init(w)

    # Multi-controller: each process contributes its rank's rows.
    rows = 256 // hvd.size()
    lo = hvd.rank() * rows
    sharding = NamedSharding(mesh, P("hvd"))
    Xs = jax.make_array_from_process_local_data(sharding, X[lo:lo + rows])
    ys = jax.make_array_from_process_local_data(sharding, y[lo:lo + rows])

    @jax.jit
    def step(w, opt_state, X, y):
        def inner(w, opt_state, X, y):
            loss, g = jax.value_and_grad(
                lambda w: jnp.mean((X @ w - y) ** 2))(w)
            upd, opt_state = tx.update(g, opt_state, w)
            return optax.apply_updates(w, upd), opt_state, loss
        return jax.shard_map(inner, mesh=mesh,
                             in_specs=(P(), P(), P("hvd"), P("hvd")),
                             out_specs=(P(), P(), P()),
                             check_vma=False)(w, opt_state, X, y)

    for _ in range(steps):
        w, opt_state, loss = step(w, opt_state, Xs, ys)
        final = float(loss)  # serializes steps; harmless on-chip
    rank = hvd.rank()
    w_err = float(np.abs(np.asarray(w) - true_w).max())
    hvd.shutdown()
    return {"rank": rank, "loss": final, "w_err": w_err}


def main():
    try:
        import pyspark  # noqa: F401
        backend = "spark"
    except ImportError:
        backend = "local"
    num_proc = int(os.environ.get("SPARK_NUM_PROC", "2"))
    results = horovod_tpu.spark.run(train, args=(8, 300), num_proc=num_proc,
                                    backend=backend,
                                    env={"JAX_PLATFORMS": "cpu",
                                         "XLA_FLAGS": ""})
    assert [r["rank"] for r in results] == list(range(num_proc))
    print("rank-ordered results:")
    for r in results:
        print(f"  rank {r['rank']}: loss {r['loss']:.6f} "
              f"w_err {r['w_err']:.4f}")
    assert all(r["w_err"] < 0.05 for r in results), "did not converge"
    print("OK")


if __name__ == "__main__":
    main()
