"""reshard_gbps: the bytes the window's resizes moved over the time their
transfers took, in GB/s (``ResizeHandler.moved_bytes`` over
``ResizeHandler.transfer_s``, summed over the window's expands and
shrinks).  ``moved_bytes`` counts what each chip of the new layout holds
and did not hold before; ``transfer_s`` is the host clock of the
program's ``reshard.transfer`` span, which includes the wait for the steps
queued before the resize.  Layer: the
reshard (``core/reshard.py`` via ``ElasticTrainer.maybe_reconfigure``).
Moves ``reconfig_s``.  A program without these fields reads nothing.
"""


def read(ctx):
    resizes = [h for h in ctx.dmr_history
               if h.action.name in ("EXPAND", "SHRINK")]
    moved = sum(getattr(h, "moved_bytes", 0) for h in resizes)
    seconds = sum(getattr(h, "transfer_s", 0.0) for h in resizes)
    if moved <= 0 or seconds <= 0:
        return None
    return moved / seconds / 1e9, "GB/s"
