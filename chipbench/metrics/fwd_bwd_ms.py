"""Device time per traced step of the forward and backward pass: the
operations under the program's ``fwd_bwd`` scope (chipbench/op_scopes.py),
on device 0, in ms."""
from chipbench import op_scopes


def read(run):
    return op_scopes.layer_ms(run, "fwd_bwd")
