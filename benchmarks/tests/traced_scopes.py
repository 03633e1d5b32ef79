#!/usr/bin/env python3
"""``traced_proposed.py`` with ``fm.expand`` (the expansion by ``slot`` and,
as ``transpose(jvp(fm.expand))``, the merge) named in its report by scope,
which that script files under ``dp.loss_grad``: one traced run of a cell,
the step's device time by named scope.

    python3 benchmarks/tests/traced_scopes.py --workload <name> --seed <n>
"""

import sys

import traced_proposed

traced_proposed.MODEL += ("fm.expand",)

if __name__ == "__main__":
    sys.exit(traced_proposed.main())
