"""Share of the window in which the chip ran no operation, in %:
``device_idle_share.join``'s reading, for the serving cell."""
import cells

read = cells.load_reader("device_idle_share.join")
