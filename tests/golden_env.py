"""Environment fingerprint for golden-history records.

Golden histories pin *bit-level* reproducibility, and CPU f32 bits are
only stable within one numerical environment: a jax/jaxlib upgrade
changes XLA codegen and random streams, and the split-model gradient
map is chaotic (parameter-Lipschitz ~1e5, docs/engine.md).  The repo
supports one installation, the goldens are recorded in it and asserted
at float precision; each golden carries the fingerprint of the
environment that recorded it so a failure names both environments.

Re-pin after an intentional toolchain upgrade with::

    PYTHONPATH=src python tests/golden/regen_bert_parity.py
"""
import platform
import sys


def fingerprint() -> dict:
    import jax
    import jaxlib
    import numpy
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "numpy": numpy.__version__,
        "python": "%d.%d" % sys.version_info[:2],
        "machine": platform.machine(),
        "backend": jax.default_backend(),
    }
