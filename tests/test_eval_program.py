"""The compiled evaluation is its own program: the benchmark's fused-round
metrics read only the scan's module, so the evaluation's device time must
never be counted as the round's."""
import re

import jax
import jax.numpy as jnp

from bench.metrics import round_device_ms
from repro.core.mlp import evaluate_classifier, init_mlp_classifier


def test_the_evaluation_program_is_not_read_as_the_scan():
    params = init_mlp_classifier(jax.random.PRNGKey(0), dim=8, hidden=4)
    hlo = evaluate_classifier.lower(params, jnp.zeros((16, 8)),
                                    jnp.zeros(16, jnp.int32)).compile()
    module = re.match(r"HloModule (\S+?),", hlo.as_text()).group(1)
    assert module.startswith("jit_evaluate")
    assert re.search(round_device_ms.SCAN_MODULE, module) is None
