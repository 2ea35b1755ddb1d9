"""JAX's own monitoring of backend compiles over the whole run:
`seconds` (cache retrievals included) or `misses` of the persistent
cache."""


def read(rec, *, field: str):
    return float({"seconds": rec.ctx.compiles.seconds,
                  "misses": rec.ctx.compiles.misses}[field])
