"""mesh engine: `collective_ms`'s formula in the star join's cell on a
mesh: the time of the collective operations in the traced window,
averaged over the traced devices, per statement completed; there, the
`psum` of each statement's group states over ICI."""

from metrics.collective_ms import read  # noqa: F401
