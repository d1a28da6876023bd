"""``python -m caps_tpu.ops.sort_validate``: differential validation of
the bitonic sort kernel against ``lax.sort``, in this process.

1. The routing validation (eager twin of the bitonic network) runs on
   whatever backend JAX has.
2. On a TPU the COMPILED pallas kernel is validated too — the evidence
   behind the ``sort`` entry of ops/kernel_table.py.

One process owns a chip: run this on its own, not beside an engine.
"""
from __future__ import annotations

import json


def main() -> int:
    import jax

    from caps_tpu.ops.sort import validate

    res = validate(compiled=False)
    ok = not res["failures"]
    out = {"routing_validation": res, "backend": jax.default_backend()}
    if jax.default_backend() == "tpu":
        resc = validate(compiled=True)
        out["compiled_validation"] = resc
        ok = ok and not resc["failures"]
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
