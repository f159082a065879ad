"""The work of one serve step's search, counted from the algorithm and not
from any kernel's tiles, so a later kernel is read against the same count.

- operations: 2 * trials * C * d, one binary multiply-accumulate per trial,
  class and dimension, over the real classes of every core on the chip (no
  padding);
- bytes: the bundled queries (trials * d/8), each slot's tenant bank
  (slots * C * d/8) and the outputs (a 4-byte distance and a 4-byte index
  per trial and core). The cores' received copies are not counted, so a
  kernel that fuses the noise with the search is not charged for them.

``least_time`` is the larger of operations over the int8 peak and bytes over
the HBM peak, and says which of the two bounds it.
"""
from __future__ import annotations


def search_work(*, slots: int, trials_per_slot: int, classes_on_chip: int,
                cores_on_chip: int, dim: int) -> tuple[float, float]:
    trials = slots * trials_per_slot
    ops = 2.0 * trials * classes_on_chip * dim
    nbytes = (trials * dim / 8 + slots * classes_on_chip * dim / 8
              + trials * cores_on_chip * 8)
    return ops, nbytes


def least_time(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    t_ops = ops / peak["int8_ops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
