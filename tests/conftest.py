import os
import sys

# src layout — tests run via ``PYTHONPATH=src pytest tests/`` but make it
# work standalone too. NOTE: never set xla_force_host_platform_device_count
# here — smoke tests and benches must see 1 device (the dry-run sets its own
# flags in a subprocess).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import pytest


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)


def assert_logits_close(got, ref):
    """The correctness contract for served logits: ``got`` equals the
    float32 reference ``ref`` within ``rtol = atol = 1e-5``.

    The engine runs the reference's arithmetic in other XLA programs
    (batched, token-padded, merged into masked tiles, fused into express
    lanes), which may sum in another order. On the reduced DeiT-Small the
    served logits stay within 2.2e-6 of the float32 masked-dense forward
    (logits up to 3.4 in magnitude), so 1e-5 is about 5x that gap. A
    request served at another keep rate, or through a wrong weight or
    token, moves its logits by orders of magnitude more."""
    import numpy as np
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
