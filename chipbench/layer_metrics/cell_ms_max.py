"""Device milliseconds per step in the slowest model cell (all classes under
one ``mpi4dl_cell<NN>``; ``tools/step_table.py`` prints every cell): where a
cell-sized change would start. First chip, from the device trace. None from
a program without the scopes."""

from chipbench.harness import step_classes


def read(context):
    cells = step_classes.cell_ms(context)
    return max(cells.values()) if cells else None
