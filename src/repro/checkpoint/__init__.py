from .manager import restore_lsm, save_lsm
