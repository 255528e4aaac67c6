"""Traffic mixes and training jobs: one data file per mix, read by the one
generator in :mod:`bench.traffic.generate`."""
