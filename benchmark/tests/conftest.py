"""The benchmark's own tests: ``python -m pytest benchmark/tests``.

Held to the CPU: the trace reader is JAX's, and the rehearsals start the
verifier service with ``JAX_PLATFORMS=cpu``, the one case in which
``require_accelerator`` lets it serve from the host."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
