"""Pallas kernels: the tiered3 front-tier queue loops and the LM layers.

TPU compiles them through Mosaic; the CPU backend (what the test suite
runs on) executes them in interpret mode.
"""

import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels must run in interpret mode here.

    ``False`` on TPU, ``True`` on the CPU backend.  Any other backend
    raises: interpreting there would quietly run a different program
    than the one the kernels were written for.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels cannot run on the {backend!r} backend: they "
        "compile on TPU and run in interpret mode on CPU only"
    )
