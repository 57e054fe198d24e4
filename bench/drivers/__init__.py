"""Traffic drivers, one module each, named by a traffic mix's ``driver``.

A mix (``bench/traffic/<name>.json``) is data: its driver's name and
the parameters that driver reads.  Each driver module has

- ``prepare(setup, mix, seed) -> plan``: the work of the window drawn
  from ``seed``, and the warm-up of the entry the window drives
  (counted as set-up);
- ``drive(setup, plan, seconds, watch, span) -> Window``: the measured
  window, every answer kept;
- ``check(setup, window, reference) -> (checks, reach)``: each answer
  against the reference, and per call the (edges, vertices) it reached.

``bench.harness`` defines ``Window``, ``Solve`` and ``Check``.
"""
