"""kernels: `scan_roofline`'s formula in the star join's cell: the least
time the chip could take for the statements of the window (bytes each must
read, from the query and configuration files: the fact table's and every
joined dimension's rows times the wire width of the columns its SQL names,
over the chip's peak HBM bandwidth) over the device-busy time inside those
statements.  The work is fixed by the two files whatever implements the
join."""

from metrics.scan_roofline import read  # noqa: F401
