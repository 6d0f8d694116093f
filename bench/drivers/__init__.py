"""Drivers: how a cell sets up, what one step of its window is, and
what its answers are.

A traffic mix names its driver (``"driver"``); the driver is the file
``bench/drivers/<driver>.py``, found by that name (``spec.driver``).
It exports one class, ``Driver``, built as ``Driver(cell, seed)``
(a ``spec.Cell`` and the run's ``--seed``), with:

* ``warm()``: every shape and program the window uses, once;
* ``step(record, traced)``: one unit of the window's work, recording
  its samples, calls and attempts in ``record`` (``bench/record.py``);
* ``memory()``: move what the check needs to the host and free the
  device;
* ``failures() -> int``: dead letters, deadlocks, non-finite answers;
* ``judge(seed, control=None) -> dict``: the numbers compared, each
  with a limit in the mix's ``limits``, from a seeded sample of what
  the window produced; with ``control`` (a dtype) the reference at
  that precision is judged in the program's place.  It leaves the
  check's summed counts in ``totals``;
* ``report(record) -> list``: lines of its own for the run's report;
* ``spans``: the names of its host spans (``record.span``), which a
  traced run reads to name the device's idle gaps;
* ``pass_k``, ``pass_j``: forks per pass on one device and jobs per
  fork, where the cell runs the batched pass (else 0).

The program gets only the generated ``JobSpec``s and the names of its
own pool and goal; the reference check gets the plain traces.
"""
