#!/bin/sh
# PR 36, calls 7 and 8 in one (no chip was free when call 7 was first asked
# for): the overlay's two traced runs first, then the hunt.
sh tools/chip_calls/pr36_08_overlay.sh
sh tools/chip_calls/pr36_07_hunt_again.sh
