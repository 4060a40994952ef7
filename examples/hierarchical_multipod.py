"""Hierarchical (client -> edge/pod -> cloud) FL on a multi-pod host mesh —
the Hier-Local-QSGD / FedPAQ periodic-averaging demo.

    PYTHONPATH=src python examples/hierarchical_multipod.py --sync-every 4

Runs on 8 virtual host devices as a (2 pods x 2 clients x 2 TP) mesh; shows
per-round pod divergence growing between cloud syncs and collapsing to zero
at each sync, plus the edge-vs-cloud wire-byte split.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse                                              # noqa: E402

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402

from repro.core.engine import run_rounds                     # noqa: E402
from repro.core.hierarchical import make_hier_fl_train_step  # noqa: E402
from repro.core.types import ArchConfig, FLConfig            # noqa: E402
from repro.data.synthetic import FedDataConfig, sample_round # noqa: E402
from repro.models.model import Model                         # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sync-every", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=12)
    args = ap.parse_args()

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    cfg = ArchConfig(name="hier-demo", family="dense", num_layers=2,
                     d_model=128, num_heads=4, num_kv_heads=2, d_ff=256,
                     vocab_size=256, block_pattern=("attn+mlp",),
                     dtype=jnp.float32, remat=False)
    model = Model(cfg)
    fl = FLConfig(algorithm="fedavg", local_steps=2, local_lr=0.2,
                  uplink_compressor="qsgd8", pod_compressor="qsgd8",
                  hierarchical=True, sync_every=args.sync_every)
    h = make_hier_fl_train_step(model, fl, mesh, chunk=32)
    state = h.init_fn(jax.random.PRNGKey(0))

    data = FedDataConfig(vocab_size=256, num_clients=4, seq_len=32,
                         batch_per_client=4, heterogeneity=2.0)

    def data_fn(r):
        b = sample_round(data, jax.random.fold_in(jax.random.PRNGKey(1), r))
        return {k: v.reshape((2, 2) + v.shape[1:]) for k, v in b.items()
                if k in ("tokens", "labels", "mask")}

    print(f"mesh={dict(mesh.shape)} params={model.param_count():,} "
          f"sync_every={args.sync_every}")
    # one scan-compiled driver: the engine's round_fn folds the edge/cloud
    # alternation into the compiled program (cond on round % sync_every)
    state, ms = run_rounds(h.engine, state, data_fn, args.rounds, chunk=8)
    print(f"{'round':>5} {'kind':>6} {'loss':>7} {'pod_div':>10} {'wireMB':>8}")
    for r in range(args.rounds):
        cloud = (r + 1) % args.sync_every == 0
        print(f"{r:>5} {'cloud' if cloud else 'edge':>6} "
              f"{float(ms['loss'][r]):>7.3f} "
              f"{float(ms['pod_divergence'][r]):>10.2e} "
              f"{float(ms['ledger'].uplink_wire[r])/1e6:>8.3f}")
    print("\npod divergence grows between syncs, resets at cloud rounds;")
    print("cloud rounds pay the extra (quantised) DCN hop — that factor of")
    print(f"{args.sync_every}x fewer cloud syncs is Hier-Local-QSGD's saving.")


if __name__ == "__main__":
    main()
