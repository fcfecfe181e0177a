"""Device time per traced step of the optimizer: the operations under the
program's ``optimizer`` scope (guard, global norm, clip, unpack and the
update; chipbench/op_scopes.py), on device 0, in ms."""
from chipbench import op_scopes


def read(run):
    return op_scopes.layer_ms(run, "optimizer")
