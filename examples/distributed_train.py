"""End-to-end driver: train a ~100M-param qwen3-family model with
PARALLEL-MEM-SGD on a data+model mesh for a few hundred steps.

This is the (b) end-to-end deliverable: real data pipeline, real mesh,
per-worker error-feedback memory, sparse all-gather gradient exchange,
checkpointing — the full stack, sized to run on this CPU container.

Run:  PYTHONPATH=src python examples/distributed_train.py \
          [--steps 300] [--devices 4] [--optimizer memsgd] [--ratio 0.01]

(--devices N > 1 forces N host platform devices; must be set before jax
 initializes, which this script does for you.)
"""
import argparse
import os
import sys

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=300)
ap.add_argument("--devices", type=int, default=4)
ap.add_argument("--data", type=int, default=None, help="data-axis size")
ap.add_argument("--model", type=int, default=None, help="model-axis size")
ap.add_argument("--optimizer", default="memsgd",
                choices=["memsgd", "memsgd_momentum", "adam_compressed",
                         "dense"])
ap.add_argument("--ratio", type=float, default=0.01)
ap.add_argument("--bucketed", action="store_true",
                help="flat-buffer bucketed sync (repro.core.buckets)")
ap.add_argument("--wire", default="unpacked", choices=["unpacked", "packed"],
                help="all-gather wire format (repro.core.encoding)")
ap.add_argument("--value-dtype", default="float32",
                choices=["float32", "bfloat16"], help="sync value dtype")
ap.add_argument("--d-model", type=int, default=512)
ap.add_argument("--layers", type=int, default=8)
ap.add_argument("--seq", type=int, default=256)
ap.add_argument("--batch", type=int, default=16)
ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
args = ap.parse_args()

if args.devices > 1:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.devices}"
    )
sys.path.insert(0, "src")

import jax  # noqa: E402  (after XLA_FLAGS)
from repro.utils.compat import make_mesh  # noqa: E402

from repro.checkpoint import Checkpointer  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.core.distributed import SyncConfig, message_bytes  # noqa: E402
from repro.data import token_batches  # noqa: E402
from repro.data.pipeline import ShardedBatcher  # noqa: E402
from repro.launch.sharding import sync_col_axes  # noqa: E402
from repro.launch.train import TrainConfig, train  # noqa: E402
from repro.models import build_model  # noqa: E402


def main():
    n_data = args.data or max(1, args.devices // 2)
    n_model = args.model or (args.devices // n_data)
    mesh = make_mesh((n_data, n_model), ("data", "model"))
    print(f"mesh: data={n_data} model={n_model}")

    # ~100M params: scale the qwen3 smoke family up
    cfg = get_smoke_config("qwen3-4b").replace(
        n_layers=args.layers, d_model=args.d_model, n_heads=8, n_kv_heads=4,
        head_dim=64, d_ff=args.d_model * 4, vocab_size=8192,
        vocab_pad_multiple=256,
    )
    model = build_model(cfg)
    n_params = model.n_params()
    print(f"arch: {cfg.n_layers}L d={cfg.d_model} vocab={cfg.vocab_size} "
          f"-> {n_params/1e6:.1f}M params")

    tc = TrainConfig(
        optimizer=args.optimizer,
        eta=0.5 if args.optimizer.startswith("memsgd") else 3e-3,
        eta_shift=200.0,
        sync=SyncConfig(ratio=args.ratio, bucketed=args.bucketed,
                        wire=args.wire, value_dtype=args.value_dtype),
    )
    shapes = model.param_shapes()
    if args.bucketed:
        from repro.core import buckets as bk
        from repro.core.distributed import bucketed_message_bytes

        plan = bk.make_plan(shapes, cols=tc.sync.bucket_cols,
                            dense_below=tc.sync.dense_below)
        msg = bucketed_message_bytes(tc.sync, plan)
    else:
        msg = message_bytes(tc.sync, shapes, sync_col_axes(shapes))
    dense = message_bytes(SyncConfig(strategy="dense"), shapes)
    print(f"sync: {args.optimizer} ratio={args.ratio} wire={args.wire} -> "
          f"{msg/1e6:.2f} MB/worker/step (dense would be {dense/1e6:.1f} MB, "
          f"{dense/max(msg,1):.0f}x reduction)")

    batches = ShardedBatcher(
        mesh, token_batches(cfg.vocab_size, args.batch, args.seq, seed=0)
    )
    ck = Checkpointer(args.ckpt_dir, max_to_keep=2)
    params, memory, opt, count, history = train(
        model, mesh, tc, batches, n_steps=args.steps, checkpointer=ck,
        ckpt_every=max(50, args.steps // 4), log_every=10,
    )
    first, last = history[0][1], history[-1][1]
    print(f"\nloss {first:.4f} -> {last:.4f} over {args.steps} steps "
          f"({'OK' if last < first else 'NO IMPROVEMENT'})")
    print(f"checkpoints: {ck.steps()} in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
