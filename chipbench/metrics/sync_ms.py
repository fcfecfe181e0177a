"""Device time per traced step of the gradient sync: the operations under
the program's ``sync`` scope and its ``encode``, ``exchange`` and
``decode`` stages (chipbench/op_scopes.py), on device 0, in ms. The stages'
split and the unscoped remainder are logged on standard error."""
from chipbench import op_scopes


def read(run):
    return op_scopes.layer_ms(run, "sync")
