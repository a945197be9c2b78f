"""What the nine ``setup_*`` readers share: the process's compile ledger
(``horovod_tpu/telemetry/compile_ledger.py``, PR 51), or None on a program
that has none, where each of them then leaves its metric out."""


def process_ledger():
    try:
        from horovod_tpu.telemetry import compile_ledger
    except ImportError:
        return None
    return compile_ledger.get_ledger()
