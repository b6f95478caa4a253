"""`scan_roofline` for a cell whose agent shards its feeds over a mesh: the
least time the mesh's memories could take to read what the traced span's
device-routed queries need, over the time a device was busy.

Bytes as `scan_roofline` counts them (the widths of the columns a query's
script reads times the rows in its time range, whatever kernel implements
it); bound: the mesh's width (the configuration's `mesh_devices`, which is
the cell's `chips`) times one chip's HBM bytes/s, so it is `scan_roofline`'s
reading over that width.  `busy_s` is the mean over those devices, and the
devices' busy times sum to at least bytes / one chip's rate however the rows
are spread over them: the share cannot pass 100%.  Nothing to read where
`scan_roofline` has nothing, or in a configuration that states no mesh."""
import scan_roofline


def read(run):
    width = int(run["config"].get("mesh_devices") or 0)
    one_chip = scan_roofline.read(run) if width else None
    return None if one_chip is None else one_chip / width
