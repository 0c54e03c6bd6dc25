"""Model workflows — TPU-native counterparts of the Znicz samples
(MNIST FC, MNIST conv, CIFAR convnet, AlexNet, MNIST autoencoder,
Kohonen SOM; ``.coveragerc:51-66``, ``manualrst_veles_algorithms.rst``),
and the token-model family: sparse-expert language models whose
modules (``latent_moe_lm``, ``window_moe_lm``, ``indexed_moe_lm``,
``conv_moe_lm``, ``prerouted_moe_lm``) each hold a published
configuration, a tiny preset and its layer descriptors, and share one
workflow, its optimizer and its token rows (``token_lm.py``).
"""
