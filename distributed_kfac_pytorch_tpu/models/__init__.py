"""Flax model zoo: the workloads the reference framework trains.

- ``cifar_resnet``: ResNet-20..1202 for CIFAR-10 (reference
  examples/cnn_utils/cifar_resnet.py).
- ``imagenet_resnet``: ResNet-18..152 for ImageNet-1k (reference uses
  torchvision models in examples/torch_imagenet_resnet.py).
- ``lstm_lm``: LSTM language model (reference examples/rnn_utils/lstm.py).
- ``transformer_lm``: Transformer decoder LM with Linear-layer K-FAC and
  optional ring-attention sequence parallelism (BASELINE config 4).
- ``mla_moe_lm``: DeepSeek-V3-shaped decoder LM — latent attention
  (MLA), sigmoid-routed stacked experts beside shared ones, SwiGLU,
  RMSNorm, RoPE, an untied head; exercises the ``experts`` layer kind.
- ``looped_lm``: looped (depth-recurrent) decoder LM — one stack of
  full-head SwiGLU layers run several times a forward with the same
  weights, an exit and a gate after every pass; every projection is one
  K-FAC layer with several calls a step (the multi-call capture path).
- ``window_moe_lm``: Mellum 2-shaped decoder LM — sliding-window and
  full attention layers mixed, grouped key/value heads, YaRN RoPE on the
  full layers, softmax-routed stacked experts with no shared one.
- ``block_diffusion_lm``: SDAR-shaped decoder LM trained by diffusion
  over blocks — a noised and a clean copy of each sequence side by
  side under a block-diffusion mask, grouped key/value heads with q/k
  RMSNorm, softmax-routed stacked experts, the loss on masked tokens.
- ``mobilenet``: MobileNetV1 — the depthwise workload the reference
  cannot precondition (no grouped-conv layer kind there); exercises
  this framework's ``conv2d_grouped`` path end to end.
- ``vit``: Vision Transformer — conv patch embed + bidirectional
  encoder blocks (shared with ``transformer_lm``), another family the
  reference has no working analogue of.
"""

from distributed_kfac_pytorch_tpu.models import block_diffusion_lm
from distributed_kfac_pytorch_tpu.models import cifar_resnet
from distributed_kfac_pytorch_tpu.models import imagenet_resnet
from distributed_kfac_pytorch_tpu.models import looped_lm
from distributed_kfac_pytorch_tpu.models import lstm_lm
from distributed_kfac_pytorch_tpu.models import mla_moe_lm
from distributed_kfac_pytorch_tpu.models import mobilenet
from distributed_kfac_pytorch_tpu.models import transformer_lm
from distributed_kfac_pytorch_tpu.models import vit
from distributed_kfac_pytorch_tpu.models import window_moe_lm
