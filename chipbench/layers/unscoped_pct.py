"""Share of the device's busy time that no region name reaches: instructions with
no `op_name`, those the trace reduction does not keep (it keeps the 300 longest),
other programs. What the region metrics leave out of `train_step_ms`."""

from layers._program_record import unscoped_pct


def read(run):
    return unscoped_pct(run)
