"""Tiny sizes of each benchmark cell, small enough for a CPU test run."""

SMALL = {
    "replay.keyspace_master": {
        "config": {"n_cells": 1024}, "traffic": {"n_ticks": 48},
    },
    "sweep.keyspace_master": {
        "traffic": {"n_scenarios": 8, "cells_per_scenario": 256,
                    "n_ticks": 32},
    },
    "directory.chubby_directory": {
        "config": {"n_shards": 1024},
        "traffic": {"episode_ticks": 112, "stall_tick": 48,
                    "failover_horizon": 40, "warm_ticks": 90},
    },
}
