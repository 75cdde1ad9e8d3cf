"""Configuration `resnet50`: the program's graph and optimizer, the
reference beside it, and the analytic FLOPs. The one module that knows both
the program (`paddle_tpu.models.resnet`) and the plain reference."""

from __future__ import annotations

from benchmarks.reference import resnet50 as reference


def program_conf(cfg):
    from paddle_tpu.models import resnet

    return resnet(depth=cfg["depth"], image_shape=tuple(cfg["image_shape"]),
                  num_classes=cfg["num_classes"], fused=False)


def reference_batch(cols: dict) -> dict:
    return {"image": cols["image"], "label": cols["label"]}


def train_flops_per_row(cfg, traffic) -> float:
    return reference.train_flops_per_row(cfg)
