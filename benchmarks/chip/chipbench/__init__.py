"""Chip benchmark of the serve engine: one harness driven by data.

``BENCHMARK.json`` at the repository root names the cells; each cell
names a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``), and each metric is read by a reader of its own
(``metrics/<name>.py``).  The modules here hold everything general:

* :mod:`chipbench.spec` -- find and load those files by name;
* :mod:`chipbench.traffic` -- the one request generator;
* :mod:`chipbench.client` -- the open-loop client on the real clock;
* :mod:`chipbench.cell` -- set-up, window, trace and verdict of one run;
* :mod:`chipbench.reference` -- the plain fp32 reference and its control;
* :mod:`chipbench.trace` -- profiler trace to device busy time;
* :mod:`chipbench.cost` -- operations and bytes from shapes.
"""
