"""repro: "Model Exploration Using OpenMOLE" (Reuillon et al., 2015) as a
production-grade multi-pod JAX framework.

Subpackages: core (workflow engine), explore (DoE), evolution (NSGA-II +
islands), ants (the paper's case-study model), models (10-arch LM zoo),
train/serve (steps + engines), data, checkpoint, runtime (sharding), kernels
(Pallas TPU), configs, launch (mesh/train/serve/explore drivers).
"""
__version__ = "1.0.0"
