"""TPU-native compute ops — the re-implementation of the reference's
kernel set (``ocl/*.cl`` + ``cuda/*.cu``, SURVEY.md §2.2) on XLA/Pallas.

==========================  ===============================================
reference kernel            this package
==========================  ===============================================
matrix_multiplication*.cl   :mod:`veles_tpu.ops.gemm` (MXU dot; PRECISION_
/ gemm via CUBLAS           LEVEL 0/1/2, a Pallas Kahan kernel for level 1)
matrix_reduce.{cl,cu}       :mod:`veles_tpu.ops.reduce`
random.{cl,cu}              :mod:`veles_tpu.ops.random` (xorshift128+ host
(xorshift1024*)             parity + counter-based ``jax.random`` fill)
fullbatch_loader.{cl,cu}    :mod:`veles_tpu.ops.gather`
mean_disp_normalizer.*      :mod:`veles_tpu.ops.normalize`
join.jcl/.jcu               :mod:`veles_tpu.ops.join`
benchmark.cl                :mod:`veles_tpu.ops.benchmark`
(none: no attention there)  :mod:`veles_tpu.ops.band_attention` (Pallas:
                            grouped heads and a window, forward, backward)
(none: no attention there)  :mod:`veles_tpu.ops.select_attention` (Pallas:
                            grouped heads under a learned selection of
                            keys: forward, probabilities, backward)
==========================  ===============================================
"""

from veles_tpu.ops.gemm import gemm  # noqa: F401
from veles_tpu.ops.reduce import matrix_reduce  # noqa: F401
from veles_tpu.ops.gather import gather_minibatch  # noqa: F401
from veles_tpu.ops.normalize import mean_disp_normalize  # noqa: F401
from veles_tpu.ops.join import join_arrays  # noqa: F401
