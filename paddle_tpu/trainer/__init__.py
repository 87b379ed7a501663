"""The trainer: `build_train_step` makes a model (any that keeps the
contract of `contract.py`), an optimizer and a mesh into one compiled
hybrid-parallel step and its state. It knows no model; no model file
knows it.

  contract.py  what is asked of a model; which parameters are blocks
  state.py     the state's layout: naming, residency, partition specs
  trunk.py     the blocks: scan, streams, pipeline; sequence parallelism
  step.py      embedding -> trunk -> loss head, gradients, the step
  offload.py   the step with the optimizer's slots at rest on the host
"""
from .contract import check_model, sync_params_to_model  # noqa: F401
from .state import flatten, unflatten  # noqa: F401
from .step import build_train_step  # noqa: F401
