"""The ``granitemoehybrid_moe`` family's gradients (``models/granite.py``
with experts) against the plain reference's, at the published widths, on the
chip: ``check_grads_granite.py`` as it is (it asks its family and its
reference by the configuration's names, and this family offers the same
``with_layers``, ``init`` and ``loss``), at the size this family's reference
fits:

    chiprun -- python benchmark/check_grads_granite_moe.py --config <configuration>

The configuration's widths, dtypes, kernels, remat, chunked loss and share of
the experts, cut to its first ``--layers`` layers (6: five state-space
layers and the first attention layer, an expert layer in each) and to one
sequence of ``--seq`` tokens, here 512 by default (two chunks of the scan,
one flash tile): the reference's literal recurrence keeps a state at every
position for its backward pass, 4 MB a position at 128 heads of 64 x 128, 2
GB a layer at 512 tokens. Tokens whose experts differ between the two sides
put their gradient on other experts, which the experts' weights and the
router show most. The report is ``chiprun_out/check_grads_granite.json``.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

from check_grads_granite import main  # noqa: E402

if __name__ == "__main__":
    main(["--seq", "512", *sys.argv[1:]])
