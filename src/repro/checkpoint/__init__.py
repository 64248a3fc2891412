"""Fault-tolerant checkpointing for communication-free chains."""
from .store import (save_checkpoint, restore_checkpoint, restore_chain,
                    latest_step, list_chains, read_manifest,
                    restore_elastic, sweep_stale, CheckpointManager,
                    AsyncCheckpointManager, CheckpointNotFoundError,
                    TORN_CHECKPOINT_ERRORS)

__all__ = ["save_checkpoint", "restore_checkpoint", "restore_chain",
           "latest_step", "list_chains", "read_manifest",
           "restore_elastic", "sweep_stale", "CheckpointManager",
           "AsyncCheckpointManager", "CheckpointNotFoundError",
           "TORN_CHECKPOINT_ERRORS"]
