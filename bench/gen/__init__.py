"""Matrix generators, one file per generator named by a configuration.

Each module has ``generate(cfg) -> dict(n, indptr, indices, data)``: CSR in
row order with sorted columns, ``int64`` row pointers, ``int32`` columns and
``float64`` values, built from the configuration alone.
"""
