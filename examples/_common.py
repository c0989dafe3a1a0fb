"""Shared example bootstrap: platform pinning.

Defaults to the CPU backend so these CPU-sized examples run anywhere; set
EXAMPLE_PLATFORM=tpu to run on an attached accelerator.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
os.environ["JAX_PLATFORMS"] = os.environ.get("EXAMPLE_PLATFORM", "cpu")

import paddle_tpu  # noqa: E402,F401 — honours JAX_PLATFORMS
