"""``chip_limits`` with the batches the ``sdar`` family trains on.

    python -m benchmark.tests.chip_limits_sdar --config sdar-30b-a3b-ep16 \
        --seq-len 4096 --seeds 12 --control-seeds 3

``chip_limits.py`` draws its batches from ``data/pattern.py`` by name;
a block-diffusion batch is ``data/block_noise.py``'s ``[S, 3, T+1]``
(ids, mask, noise level). This puts that generator in the other's place
for the one process and runs ``chip_limits.main`` as it is: the same
seeds, the same two numbers, the same file under ``chiprun_out/``.
"""

from benchmark.data import block_noise, pattern
from benchmark.tests import chip_limits

if __name__ == "__main__":
    pattern.batch = block_noise.batch
    raise SystemExit(chip_limits.main())
