"""Device-side layer names of the train step (DESIGN §12).

Each name is opened with ``jax.named_scope`` around one layer of the step,
so it lands in the ``op_name`` of every instruction that layer compiles to,
and in the ``tf_op`` of every operation of a device trace. Scopes only add
metadata: the optimized program is the same with or without them.
"""
FWD_BWD = "fwd_bwd"        # loss and its gradient (train/trainer.py)
SYNC = "sync"              # gradient accumulation, sync engine, recovery
ENCODE = "encode"          # sync stages (core/pipeline.py, core/allreduce.py)
EXCHANGE = "exchange"
DECODE = "decode"
OPTIMIZER = "optimizer"    # guard, global norm, clip, unpack, update

ALL = (FWD_BWD, SYNC, ENCODE, EXCHANGE, DECODE, OPTIMIZER)
