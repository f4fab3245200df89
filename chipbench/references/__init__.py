"""Model kinds, one module per kind of decoder layer, found by name: a
configuration's ``"reference"`` names ``references/<reference>.py``, so a
new kind is one new file here. Each module provides:

- ``program_params(cfg, seed)``: the program's parameter tree, drawn on
  the device in one jitted call;
- ``Reference(cfg, ring, seed)``, a ``chipbench.reference.Reference``
  with its layer, whose ``.gaps(seqs, control)`` the check calls. Its
  ``layer_group(layer)`` names the group of layers whose weights share
  their shapes (one group unless the kind overrides it), and
  ``layer_weights(layer, group)`` is compiled once per group;
- ``Work(cfg, ring)``: ``params``, ``weight_bytes``, ``step_weight_bytes``,
  ``kv_bytes_per_token``, ``decode_lane_bytes``, ``decode_lane_flops``,
  ``decode_step_least_s`` and ``prefill_flops``, for the readers.

Two rules hold for every kind. The program's weights and the reference's
come from one definition per layer, keyed by ``weights.layer_key``. Work
counts are lower bounds of the published algorithm's work, so no share of
a roofline or of a peak can pass 100%.
"""
