"""kernels: `mesh_scan_roofline`'s formula over `star_roofline`'s bytes,
in the star join's cell on a mesh: the least time the mesh's chips could
take for the statements of the window (the fact table's and every joined
dimension's rows times the wire width of the columns its SQL names, from
the query and configuration files, over the traced devices' count times
one chip's peak HBM bandwidth) over the device-busy time inside those
statements on the first device."""

from metrics.mesh_scan_roofline import read  # noqa: F401
