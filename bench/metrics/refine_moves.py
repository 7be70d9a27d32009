"""Phase-2 trades per job: the program's ``refine_moves`` counter, averaged
over the window's jobs. None where the program reports no such counter."""
import numpy as np


def read(run):
    moves = [r["telemetry"]["refine_moves"] for r in run.records
             if "refine_moves" in r["telemetry"]]
    return float(np.mean(moves)) if moves else None
