"""Share of the window in which the cell's chips ran no operation, in %,
averaged over the chips: ``device_idle_share.join``'s reading, for the
cells that report ``join_vectors_per_s.dist``."""
import cells

read = cells.load_reader("device_idle_share.join")
