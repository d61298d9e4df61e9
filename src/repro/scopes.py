"""Stable names of the GW solve's stages and kernels on the device.

Each stage of a mirror-descent solve is traced under one `jax.named_scope`
below.  A scope only writes into the ``op_name`` metadata of the HLO ops the
stage makes: the compiled program is the same with or without it, and it
costs nothing at run time.  A profiler trace carries that metadata as each
device op's ``tf_op`` (``jit(...)/gw.driver/while/body/gw.grad/...``), so a
trace reader can split device time by stage, keyed by the innermost ``gw.``
component.  Every stage lives inside the one step body that the one-shot,
batched and segmented (served) solves share, so all three carry the same
names.

The Pallas kernels are named too (``pl.pallas_call(..., name=...)``), so a
trace lists them under these names whatever their Python wrappers are
called.
"""

#: the mirror-descent gradient C = C1 − 4·D_X Γ D_Y (FGC on grids), with
#: its axis moves; the factored plan's (∇Q, ∇R, ∇g)
GRAD = "gw.grad"
#: the entropic projection: Sinkhorn half-steps, residual checks and plan
#: formation; the factored plan's Dykstra projection
SINKHORN = "gw.sinkhorn"
#: the plan's L1 change between outer steps
DELTA = "gw.delta"
#: the outer loop's bookkeeping: carry masking, the ε schedule, counters
DRIVER = "gw.driver"
#: the value and energy assembly after the loop
VALUE = "gw.value"
#: the initial plan and the constant gradient term before the loop
INIT = "gw.init"

STAGES = (GRAD, SINKHORN, DELTA, DRIVER, VALUE, INIT)

#: Pallas kernel names, one per ``pl.pallas_call``
SINKHORN_ROW_KERNEL = "gw_sinkhorn_row"
SINKHORN_COL_KERNEL = "gw_sinkhorn_col"
LR_DYKSTRA_HALF_KERNEL = "gw_lr_dykstra_half"
LR_GRAM_CHAIN_KERNEL = "gw_lr_gram_chain"
LR_GRAD_COMBINE_KERNEL = "gw_lr_grad_combine"
FGC_DTILDE_KERNEL = "gw_fgc_dtilde"
FGC_L_KERNEL = "gw_fgc_l"
