"""Distributed classifier training: dataset -> prefetch -> sharded steps.

    python examples/train_classifier.py --data-dir ./imgs --labels labels.json \
        --model ResNet50 --dp 4 --tp 2 --epochs 3 --ckpt /tmp/ckpt

`labels.json` maps file name -> integer class. On a CPU box, set
XLA_FLAGS=--xla_force_host_platform_device_count=8 to get a virtual
mesh. Checkpoints are resume-exact (params + optimizer + step).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import json


def main() -> None:
    from dml_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--data-dir", required=True)
    p.add_argument("--labels", required=True, help="json: {file: class_idx}")
    p.add_argument("--model", default="ResNet50")
    p.add_argument("--dp", type=int, default=-1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--ckpt", default=None, help="checkpoint dir (resumes if present)")
    args = p.parse_args()

    import jax.numpy as jnp

    from dml_tpu.data import ImageDataset, Prefetcher
    from dml_tpu.models.registry import get_model
    from dml_tpu.parallel.mesh import local_mesh
    from dml_tpu.parallel.train import Trainer

    with open(args.labels) as f:
        labels = json.load(f)
    samples = [
        (os.path.join(args.data_dir, name), int(cls))
        for name, cls in sorted(labels.items())
    ]
    spec = get_model(args.model)
    ds = ImageDataset(samples, spec.input_size, args.batch_size)
    if len(ds) == 0:
        raise SystemExit(
            f"dataset has {len(samples)} samples — fewer than "
            f"--batch-size {args.batch_size} (full batches are dropped)"
        )

    mesh = local_mesh(dp=args.dp, tp=args.tp)
    tr = Trainer(
        args.model, mesh, batch_size=args.batch_size,
        learning_rate=args.lr, num_classes=args.num_classes,
        dtype=jnp.bfloat16,
    )
    if args.ckpt and os.path.exists(os.path.join(args.ckpt, "manifest.json")):
        step = tr.restore_checkpoint(args.ckpt)
        print(f"resumed from step {step}")

    for epoch in range(args.epochs):
        for images, lab in Prefetcher(ds, epoch=epoch):
            m = tr.step(images, lab)
        print(f"epoch {epoch}: loss={m['loss']:.4f} acc={m['accuracy']:.3f} "
              f"({tr.last_step_time:.3f}s/step)")
        if args.ckpt:
            tr.save_checkpoint(args.ckpt)


if __name__ == "__main__":
    main()
