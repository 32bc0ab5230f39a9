"""The chip benchmark's own code: the yardstick that later changes cannot move."""
