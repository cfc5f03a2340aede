"""Child bootstrap for ``MultiProcessRunner`` workers.

Argv: ``target("module:function") rank payload_json``.  Configures the CPU
backend *before* any device API call, joins the cluster per the env the runner injected, runs the worker
fn, and emits its JSON result on stdout behind ``TTD_RESULT:``.
"""

import importlib
import json
import os
import sys


def main() -> int:
    target, rank_s, payload_json = sys.argv[1], sys.argv[2], sys.argv[3]
    rank = int(rank_s)
    payload = json.loads(payload_json)

    from tensorflow_train_distributed_tpu.runtime.mesh import force_platform

    force_platform(
        "cpu", int(os.environ.get("TTD_TEST_LOCAL_DEVICES", "2")))

    if os.environ.get("TTD_TEST_INIT_DISTRIBUTED") == "1":
        from tensorflow_train_distributed_tpu.runtime.distributed import (
            initialize_distributed,
        )

        initialize_distributed()

    mod_name, _, fn_name = target.partition(":")
    fn = getattr(importlib.import_module(mod_name), fn_name)
    result = fn(rank, **payload)
    print("TTD_RESULT:" + json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
