"""Worker driven by tests/test_device_observability.py.

A real OS process that consumes a small libsvm file through
``DeviceRowBlockIter``, holding each batch for 20 ms. After a dozen batches
it prints ``READY``; the parent then stops the whole process with
``SIGSTOP`` for a while and lets it go on, so one hold runs long with every
thread of the process frozen: both pulses wake late. At the end the
``long_hold`` events are printed as one JSON line.

Usage: python hold_worker.py <repo_root> <data_uri>
"""

import json
import sys
import time


def main() -> None:
    repo, uri = sys.argv[1], sys.argv[2]
    sys.path.insert(0, repo)
    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.tpu.device_iter import DeviceRowBlockIter

    with DeviceRowBlockIter(uri, batch_rows=64, min_nnz_bucket=128,
                            layout="csr") as it:
        for i, _batch in enumerate(it):
            if i == 12:
                print("READY", flush=True)
            time.sleep(0.02)
    print(json.dumps([e for e in telemetry.events()
                      if e["event"] == "long_hold"]), flush=True)


if __name__ == "__main__":
    main()
