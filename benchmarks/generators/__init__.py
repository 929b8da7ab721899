"""The benchmark's data generators, one module a schema, found by the name a
configuration gives under `"generator"`.  Each has `DICTIONARIES` (column
to its sorted strings; such a column comes as codes into them) and
`blocks(tables, sf, seed)`, which yields (table, {column: array}) in load
order for the tables asked for, the same arrays for the same seed."""
