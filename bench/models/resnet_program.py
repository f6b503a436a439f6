"""How the program builds a ResNet configuration's training loss: its own
``repro.models.resnet`` at the configuration's architecture, GroupNorm
groups, classes and LoRA rank and alpha."""
from __future__ import annotations


def loss_fn(cfg: dict):
    """``loss_fn(frozen, train, batch) -> (loss, metrics)`` of the program."""
    from repro.core.lora import LoRAConfig
    from repro.models.resnet import ResNetConfig, loss_fn as program_loss
    rc = ResNetConfig(arch=cfg["arch"], n_classes=cfg["n_classes"],
                      gn_groups=cfg["gn_groups"],
                      lora=LoRAConfig(rank=cfg["lora_rank"],
                                      alpha=cfg["lora_alpha"]))
    return lambda frozen, train, batch: program_loss(frozen, train, rc,
                                                     batch)
