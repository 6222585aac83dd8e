"""Walking a live thread's Python frames: the one frame walk the worker's
``dump_stacks`` / ``profile_stacks`` hooks and the trainer's overdue-report
sample share."""
from __future__ import annotations

import os
import sys
import threading
import traceback
from typing import List, Optional


def frame_lines(frame, limit: Optional[int] = None) -> List[str]:
    """``function (file:line)`` of a frame and its callers, innermost first,
    at most ``limit`` of them."""
    out: List[str] = []
    while frame is not None and (limit is None or len(out) < limit):
        code = frame.f_code
        out.append(
            f"{code.co_name} "
            f"({os.path.basename(code.co_filename)}:{frame.f_lineno})"
        )
        frame = frame.f_back
    return out


def thread_frames(skip: Optional[int] = None) -> dict:
    """thread ident -> its current frame, without the thread ``skip``."""
    frames = sys._current_frames()
    frames.pop(skip, None)
    return frames


def format_all() -> str:
    """Every thread's stack as ``traceback`` prints one, under its name."""
    names = {th.ident: th.name for th in threading.enumerate()}
    return "".join(
        f"--- thread {names.get(tid, '?')} ({tid}) ---\n"
        + "".join(traceback.format_stack(frame))
        for tid, frame in thread_frames().items()
    )
