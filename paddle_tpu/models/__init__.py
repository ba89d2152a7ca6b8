"""Model zoo, built on the paddle_tpu layer API.

Mirrors /root/reference/benchmark/fluid/models/ (mnist, resnet, vgg,
machine_translation) plus the distributed-test models
(unittests/dist_transformer.py, dist_ctr.py), BERT-base MLM and
DeepFM/Wide&Deep. Every model is a pure
program-builder: call inside a fluid.program_guard and it appends ops to
the current main/startup programs, returning the loss/feed variables.
"""

from . import (gpt, mnist, resnet, se_resnext, vgg, transformer, bert, ctr,
               stacked_lstm, machine_translation, vit)

__all__ = ["gpt", "mnist", "resnet", "se_resnext", "vgg", "transformer",
           "bert", "ctr", "stacked_lstm", "machine_translation", "vit"]
