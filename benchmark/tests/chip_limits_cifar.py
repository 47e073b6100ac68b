#!/usr/bin/env python3
"""``chip_limits.py`` for the cell ``cifar-rp.fit``, with one control more
than its table has: ``no_patch_norm``, the plain reference at ``highest``
WITHOUT the per-patch normalisation of the image patches, put in the
program's place.  A comparison that passed it could not tell the published
featurizer from the older port's; ``reference/cifar_random_patch.py`` reads
the switch from the precision it is handed.  Same arguments as
``chip_limits.py``:

    python3 benchmark/tests/chip_limits_cifar.py --workload cifar-rp.fit --seeds 1,2,3 \
        --controls all_lower,gram_high,gram_bf16,no_patch_norm --faults half_batch,answer_altered
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.tests import chip_limits  # noqa: E402

chip_limits.CONTROLS["no_patch_norm"] = {
    "solver": "highest", "other": "highest", "normalize_patches": False,
}

if __name__ == "__main__":
    sys.exit(chip_limits.main())
