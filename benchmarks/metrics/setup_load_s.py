"""set-up: generate + bulk_load_arrays + split (+ analyze where the
configuration asks for it)."""


def read(run):
    return run["setup"].get("load_s")
