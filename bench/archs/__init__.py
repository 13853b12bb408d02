"""Architecture modules: one per kind of model, named by a configuration
file's ``reference`` key (``bench/configs/<config>.json``), which also
names the plain reference that runs this module's weight tree
(``bench/references/<reference>.py``), and found by that name alone
(``bench.model.arch``), so that a new architecture is a new file here.

A module gives

- ``Dims``: the sizes the weights, the reference and the work counts
  need, built from the file's ``model`` keys by ``Dims.of(model)``. It
  has ``layers``, ``d`` (hidden size), ``vocab``, ``heads``,
  ``kv_heads`` and ``head_dim``, and
  - ``weight_shapes()``: the tree of leaf shapes, in the layout the
    program takes;
  - ``scale(path)``: the standard deviation of the leaf at ``path``
    (norm gains excepted: ``bench.model.make_weights`` draws every leaf
    whose path holds ``norm`` around 1);
  - ``layer_matmul_params(layer)``: the matmul weights one token meets
    in that layer (for a sparse-expert layer: its router, the experts
    it is routed to and any shared expert, not every expert);
  - ``keys_read(layer, pos)``: the keys a query at position ``pos``
    reads in that layer (``pos`` an int or an integer array, answered
    elementwise): ``pos + 1`` under full causal attention,
    ``min(pos + 1, window)`` in a windowed layer.
- ``program_config(conf)``: the program's ``ModelConfig`` for the
  configuration file ``conf``, or a ``ValueError`` where the program
  cannot serve it as stated.

``bench.work`` sums the work counts over layers.
"""
