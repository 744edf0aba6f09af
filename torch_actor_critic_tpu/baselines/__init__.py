"""Measured-baseline implementations (the reference publishes no
numbers, BASELINE.md): independent PyTorch code used by
``scripts/parity_run.py`` (return-parity baseline) and by the tests
that compare update numerics against torch
(``tests/test_parity_torch.py``)."""

from torch_actor_critic_tpu.baselines.torch_sac import (  # noqa: F401
    build_torch_sac,
    build_torch_visual_sac,
)
