#!/usr/bin/env python
"""Vision classification fine-tune — hapi Model + DataLoader recipe.

    python examples/vision_finetune.py            # single device
    python examples/vision_finetune.py --process-workers
                                                  # GIL-free transforms

Covers: ResNet (channels-last on TPU), transforms, DataLoader (thread or
process workers), hapi Model.fit/evaluate, amp O2, checkpoint save.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


class SyntheticImages:
    """Stand-in for an image-folder dataset (zero-egress environment)."""

    def __init__(self, n=128, size=32, classes=10, transform=None,
                 channels_last=False):
        rng = np.random.RandomState(0)
        self.x = rng.rand(n, size, size, 3).astype(np.float32)
        self.y = rng.randint(0, classes, (n, 1)).astype(np.int64)
        self.transform = transform
        self.channels_last = channels_last

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        img = self.x[i]
        if self.transform is not None:
            img = self.transform(img)
        if not self.channels_last:
            img = img.transpose(2, 0, 1)
        return img, self.y[i]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--process-workers", action="store_true")
    ap.add_argument("--epochs", type=int, default=1)
    args = ap.parse_args()

    import jax
    import paddle_tpu as paddle
    from paddle_tpu.vision import transforms as T

    on_tpu = jax.default_backend() == "tpu"
    transform = T.Compose([T.Normalize(mean=[0.5, 0.5, 0.5],
                                       std=[0.5, 0.5, 0.5],
                                       data_format="HWC")])
    # channels-last end to end on TPU: dataset layout matches the MXU conv
    # layout, no transposes anywhere
    train = SyntheticImages(n=64, transform=transform, channels_last=on_tpu)
    val = SyntheticImages(n=32, transform=transform, channels_last=on_tpu)

    model = paddle.vision.models.resnet18(
        num_classes=10, data_format="NHWC" if on_tpu else "NCHW")
    opt = paddle.optimizer.Momentum(0.01, parameters=model.parameters())

    m = paddle.Model(model)
    m.prepare(optimizer=opt, loss=paddle.nn.CrossEntropyLoss(),
              metrics=paddle.metric.Accuracy(),
              **({"amp_level": "O2", "amp_dtype": "bfloat16"}
                 if on_tpu else {}))
    loader_kw = dict(batch_size=16, num_workers=2)
    if args.process_workers:
        loader_kw["worker_mode"] = "process"
    train_loader = paddle.io.DataLoader(train, shuffle=True, **loader_kw)
    val_loader = paddle.io.DataLoader(val, **loader_kw)

    m.fit(train_loader, val_loader, epochs=args.epochs, verbose=1)
    res = m.evaluate(val_loader, verbose=0)
    print("eval:", res)
    m.save("/tmp/vision_ckpt/final")
    print("saved /tmp/vision_ckpt/final.pdparams")


if __name__ == "__main__":
    main()
