"""The fixture ``tiny``'s reference, found under the tests' own data
directory as a run finds it under ``bench/``: the layer of
``bench/reference/dense_lm.py``, taken over name by name."""
from lib import spec

_dense_lm = spec.module("reference", "dense_lm")

tensor_specs = _dense_lm.tensor_specs
param_specs = _dense_lm.param_specs
logits = _dense_lm.logits
PROGRAM_FIELDS = _dense_lm.PROGRAM_FIELDS
PROGRAM_FIXED = _dense_lm.PROGRAM_FIXED
PROGRAM_CONTEXT = _dense_lm.PROGRAM_CONTEXT
PROGRAM_LAYER = _dense_lm.PROGRAM_LAYER
