"""Data layer: host-side ingestion + partitioning, device-resident batching.

Replaces the reference's torchvision/DataLoader stack (image_helper.py:173-296,
loan_helper.py:29-210) with:

- raw-file dataset loaders (MNIST idx, CIFAR-10 pickle, Tiny-ImageNet folders,
  LOAN per-state CSVs) plus deterministic synthetic fallbacks for machines
  without the datasets (zero-egress environments, CI);
- numerically-parity-preserving client partitioning (Dirichlet / equal /
  per-US-state natural shards);
- *batch plans*: precomputed [clients, epochs, steps, batch] index tensors into
  a device-resident dataset, so a whole FL round's data access is one gather —
  no host↔device transfer in the hot loop.
"""
from dba_mod_tpu.data.datasets import (ImageData, LoanData, load_image_dataset,
                                       load_loan_dataset)
from dba_mod_tpu.data.partition import (equal_split_indices,
                                        sample_dirichlet_indices)
from dba_mod_tpu.data.batching import (BatchPlan, EvalPlan, build_batch_plan,
                                       build_eval_plan, plan_step_counts)
