"""JAX/TPU kernels for the block-verification hot path.

This package is the TPU-native replacement for the reference's CPU crypto
(``mysticeti-core/src/crypto.rs:174-189`` verify_block): batched Ed25519
verification expressed as int32 limb arithmetic that XLA vectorizes on the
TPU VPU, ``vmap``ped over the signature batch and shardable across chips with
``shard_map`` (see ``mysticeti_tpu.parallel``).

Modules:
  field    — GF(2^255-19) arithmetic in 20x13-bit int32 limbs
  ed25519  — twisted-Edwards point ops + the batched verify kernel
  sha512   — SHA-512 compression in 32-bit lanes (fused digest+verify path)

Importing this package is what brings JAX into a process, so it is also where
the persistent compilation cache is placed — before any submodule builds its
module-level ``jnp`` constants (the first array latches JAX's cache setup).
Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no path is
set here; otherwise the cache is one fixed, git-ignored directory at the root
of the checkout.  The path is part of what a deployment shares between the
processes that compile (one verifier service per chip, tools, tests): a
directory that moved per user, host or run would never hit.

So is the cache KEY.  It covers each Pallas kernel as serialized into its
custom call — Mosaic bytecode WITH its MLIR locations, which by default are
ten-frame Python tracebacks.  The same kernel reached from another call site
(the service, ``bench.py``, ``chip_smoke.py``'s verifier leg) or from a
checkout at another path therefore hashed differently, and on the v5e every
process paid the full compile again beside a cache that held the kernel
(PR 21: a service booted after the verifier leg missed on all 12 kernels).
One user frame per location, with the checkout prefix stripped, makes the key
a function of the kernel alone.
"""
import os
import re

import jax

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")

# jax.config already holds JAX_COMPILATION_CACHE_DIR when the variable is set.
if not jax.config.jax_compilation_cache_dir:
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
jax.config.update("jax_include_full_tracebacks_in_locations", False)
if not jax.config.jax_hlo_source_file_canonicalization_regex:
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex",
        re.escape(_CHECKOUT + os.sep),
    )


def compilation_cache_dir() -> str:
    """Where this process keeps compiled kernels."""
    return jax.config.jax_compilation_cache_dir
