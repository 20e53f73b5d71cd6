"""Names in the program's device trace that the metric readers key on.

Read off a TPU v5 lite trace by hand (PERF.md, "Layers"). An ``XLA Ops``
event is named ``%<instruction> = <shape> <opcode>(<operands>)``: the fused
join's Pallas kernel is the custom call ``%_fused_join_hits_pallas.<n> =
(...) custom-call(...)``, one event per launch (the pad and copy of the
points that the same jitted program does around it are ops of their own).
Programs on the ``XLA Modules`` line are named ``jit_<function>(<id>)``:
the self-join's pair emit is ``jit__emit_from_hits(...)``, and its window
planning the per-cell descriptor tables and capacities
(``jit__cell_window_table_device``, ``jit__cell_window_caps_device``) and
the per-launch descriptor gathers (``jit__fused_*prep``).
"""
KERNEL_OP_PREFIX = "%_fused_join_hits_pallas"
EMIT_MODULES = ("jit__emit_from_hits(",)
PLAN_MODULES = ("jit__cell_window_table_device(",
                "jit__cell_window_caps_device(", "jit__fused_table_prep(",
                "jit__fused_table_bucket_prep(", "jit__fused_prep(",
                "jit__fused_bucket_prep(")
