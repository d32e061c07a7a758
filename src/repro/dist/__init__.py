"""Distribution layer: sharding rules, pipeline parallelism and NVFP4
gradient compression.

Programs here call `jax.shard_map` directly. The mesh-sharded serving step
(`serve/decode.py::shard_serve_step`) is manual over "data" only
(`axis_names={"data"}`), leaving every other mesh axis under GSPMD; the
GPipe and gradient-compression programs are manual over all axes.
Repo-wide distribution conventions (the OOB-high scatter-sentinel rule,
the row-local decode contract) are recorded in docs/CONVENTIONS.md.
"""
