"""A family's own counts of required work, one file a family.

``chipbench/costs/<family>.py`` (``<family>`` as the configuration file's
``family`` says, the name ``chipbench/reference/<family>.py`` has) is found
by ``importlib`` from ``chipbench/flops.py``. It may define any of:

- ``layer_forward(tcfg, i, layer_tree, t, stats)``: one row of ``t`` real
  tokens through layer ``i``, forward. Returns ``{"matmuls": {leaf path:
  FLOPs}, "mix": FLOPs}``: ``matmuls`` the products with a parameter, keyed
  by the leaf's path inside the layer's tree (a tuple of keys), so that the
  trainer's mask decides which of them pay a weight gradient; ``mix`` the
  work with no parameter (score and value products, a scan), whose backward
  costs twice its forward. ``stats`` holds the cycle's medians of the step
  records the counts read (``moe/held_frac``).
- ``flash_fwd(model, cycle)``, ``flash_bwd(model, cycle)``,
  ``moe_gmm(model, cycle)``, or a kernel of its own under a new name: the
  REQUIRED operations and bytes of one cycle as a list of phases
  ``{"phase", "flops", "bytes"}``, for a metric whose file says
  ``"reducer": "trace_op_roofline", "costs": "<name>"``.

Absent, ``flops.py``'s generic walk and kernel costs run. A later PR counts
a block the walk has never seen by ADDING a file here; it edits nothing.
"""
