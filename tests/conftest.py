"""Test harness config.

Tests never assume real TPU hardware: JAX is forced onto CPU with 8 virtual
devices so multi-chip sharding (mesh + all-to-all fingerprint routing) is
exercised exactly as the driver's ``dryrun_multichip`` does.  Must run before
jax is used anywhere.

Note the env override must be unconditional: on a machine with a chip JAX
defaults to the TPU (and ``JAX_PLATFORMS`` may say so explicitly), and a
``setdefault`` would silently leave the whole suite running on one real
chip — which has one device, not the eight the sharding tests need, and
belongs to one process at a time.  ``jax.config.update`` pins the same
choice in case jax was imported before this file.
"""

import os
import re
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
flags += " --xla_force_host_platform_device_count=8"
# Tests are compile-time-bound (dozens of engine variants), not
# run-time-bound, and their correctness oracle is host Python — so XLA's
# CPU backend optimizations only cost wall clock here (~23% of the fast
# tier).  Long-running deep-parity jobs (the daily slow+medium CI tier,
# where RUN time dominates) opt back in via STATERIGHT_TPU_TEST_OPT=1.
if not os.environ.get("STATERIGHT_TPU_TEST_OPT"):
    flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = flags.strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert len(jax.devices()) == 8, (
    f"expected 8 virtual CPU devices, got {jax.devices()}"
)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """``medium`` implies ``slow`` for selection: pytest.ini documents
    medium as "run with the daily slow tier", so the fast tier's
    ``-m 'not slow'`` must deselect it without every harness having to
    spell ``not slow and not medium``.  The daily tier's ``slow or
    medium`` selection is unaffected, and every medium test keeps a
    cheaper fast-tier sibling (the re-tiering discipline)."""
    for item in items:
        if "medium" in item.keywords:
            item.add_marker(pytest.mark.slow)
