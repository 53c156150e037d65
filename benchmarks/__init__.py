"""Benchmark harness: one module per paper table/figure.

  accuracy.py      Table II   (trace-sim vs cycle-accurate oracle)
  pareto_fronts.py Fig. 3     (frontiers on selected designs)
  improvement.py   Fig. 4     (alpha=0.7 point vs both baselines)
  runtime.py       Table III  (search runtime vs estimated co-sim)
  convergence.py   Fig. 5     (iso-runtime convergence, k15mmtree)
  case_study.py    Fig. 6     (FlowGNN-PNA DDCF case study)
  batched_eval.py  beyond-paper evaluator throughput
  pruning.py       beyond-paper sound lower-bound pruning

Run everything: PYTHONPATH=src python -m benchmarks.run   (FULL=1 for the
full-budget versions used in EXPERIMENTS.md).
"""
