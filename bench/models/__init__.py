"""One module per architecture, named by a configuration file's
``reference`` key and imported by its path (``run.reference``).

A module exports every name in ``INTERFACE``. Each takes the
configuration's ``model`` group (a mapping; the check passes a hashable
view of it):

* ``make_params(model, seed)``: random weights in the program's parameter
  layout, made on the device in one jitted call from the seed;
* ``arch_config(model)``: the program's ``ArchConfig``;
* ``embed(params, model, tokens, positions)``,
  ``residual_stream(params, model, tokens, positions, valid, *, precision)``,
  ``forward(params, model, tokens, positions, valid, *, precision)``,
  ``each_layer(params, model, xs, codes, valid, *, precision)`` and
  ``head(params, model, x, *, precision)``: the plain reference that decides
  ``correct`` (``check.py``), at ``precision`` ``"highest"`` or the
  control's ``"high"``;
* ``kernel_flops``, ``kernel_bytes`` and ``edit_flops``, each
  ``(model, n, replaced, inserted, deleted)``: the floors of one edited
  document's work behind the roofline and MFU metrics (``work.py``).

Outside this package the harness reads only the ``model`` keys ``name``,
``n_layers``, ``d_model``, ``vocab`` and ``vq_heads``, and the served
state's ``x`` and ``codes``; every other key is the module's own.
"""

INTERFACE = ("make_params", "arch_config", "embed", "residual_stream",
             "forward", "each_layer", "head", "kernel_flops", "kernel_bytes",
             "edit_flops")
