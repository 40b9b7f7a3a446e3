"""Flax model zoo + TpflModel builders.

The reference ships one example model per framework (torch MLP
``lightning_model.py:118``, keras MLP ``keras_model.py:121``, flax MLP
``flax_model.py:171``) plus the fork's metric-extended MLP
(``mlp_pytorch.txt``). Here the zoo is all flax.linen, sized for the
benchmark ladder (MNIST MLP → CIFAR CNN → ResNet-18), with a
``compute_dtype`` knob so matmuls run bfloat16 on the MXU while params
stay float32.
"""

from tpfl.models.mellum import MellumLM
from tpfl.models.sambay import SambaYLM
from tpfl.models.zaya import ZayaLM
from tpfl.models.zoo import (CNN, MLP, ResNet18, TransformerBlock,
                             TransformerLM, create_model)

__all__ = ["MLP", "CNN", "ResNet18", "TransformerBlock",
           "TransformerLM", "SambaYLM", "MellumLM", "ZayaLM", "create_model"]
