"""TPU-native distributed RNN training framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of the reference
project ``jkhlr/pytorch-distributed-rnn`` (a PyTorch/MPI/Horovod/RPC
data-parallel RNN trainer for a Raspberry-Pi cluster; see
``/root/reference/src/motion/main.py:16``):

Subpackages (``models``, ``ops``, ``parallel``, ``data``, ``training``,
``runtime``, ``utils``) each carry their own docstring describing the
reference capability they re-implement and the TPU-native design chosen.
"""

__version__ = "0.1.0"
